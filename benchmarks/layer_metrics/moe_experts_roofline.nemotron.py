"""``moe_experts_roofline`` (see ``moe_experts_roofline.py``: the same
events, the same share of the decode programs' time) for the state-space /
expert decoder, whose experts are TWO matrices (up, down; relu squared
between them, no gate): the least time is
``nemotron_h_counts.touched_experts_bytes`` at the memory bandwidth."""

from benchmarks.harness.metrics import counter_delta
from benchmarks.layer_metrics.hybrid_decode_trace import decode_step_ms
from benchmarks.layer_metrics.moe_experts_roofline import (
    STEPS,
    TOUCHED,
    experts_share,
)
from benchmarks.references import nemotron_h, nemotron_h_counts as counts


def read(run):
    share = experts_share(run.trace)
    step_ms = decode_step_ms(run.trace)
    steps = counter_delta(run, STEPS)
    touched = counter_delta(run, TOUCHED)
    if (run.peaks is None or not share or not step_ms or steps <= 0
            or touched <= 0):
        return None
    least_s = (counts.touched_experts_bytes(
        nemotron_h._shape(run.sizes), touched / steps)
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (share * step_ms / 1e3)
