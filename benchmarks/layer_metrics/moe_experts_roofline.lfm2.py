"""``moe_experts_roofline`` (see ``moe_experts_roofline.py``) for the mixed
conv/attention decoder: the same reading at ``lfm2_moe``'s shape (64 experts
of 2048 x 1536, every one held)."""

from benchmarks.layer_metrics.moe_experts_roofline import roofline
from benchmarks.references import lfm2_moe


def read(run):
    return roofline(run, lfm2_moe._shape(run.sizes))
