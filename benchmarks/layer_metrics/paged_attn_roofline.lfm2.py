"""Least time the chip could take for the paged-attention calls of the
traced span (the larger of bytes / bandwidth and FLOPs / peak of one call,
``lfm2_moe_counts.paged_attention_call``: the live keys and values of ONE
attention layer, 512-lane rows, 32 query heads in 8 groups; a step makes one
call an attention layer) over the time they took.  Percent.

The calls are the trace's Pallas kernel events named
``paged_decode_attention`` (the kernel's ``name``): in this model XLA's own
grouped expert product (``ragged-dot``) is a ``tpu_custom_call`` too, 24 a
step, so the custom-call mark alone does not tell the kernel."""

from benchmarks.harness.trace_reduce import (
    kernel_events,
    live_kv_tokens,
    total_s,
)
from benchmarks.references import lfm2_moe, lfm2_moe_counts as counts

KERNEL_NAME = "paged_decode_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = [e for e in kernel_events(run.trace) if KERNEL_NAME in e[0]]
    kernel_s = total_s(calls)
    if not calls or kernel_s <= 0:
        return None
    need = counts.paged_attention_call(
        lfm2_moe._shape(run.sizes), live_kv_tokens(run), run.slots)
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * len(calls) * least / kernel_s
