"""Least time the chip could take for the paged-attention calls of the
traced span (the larger of bytes / bandwidth and FLOPs / peak of one call,
``nemotron_h_counts.paged_attention_call``: the live keys and values of the
ONE attention layer, 256-lane rows, 32 query heads in 2 groups of 16; a step
makes one call) over the time they took.  Percent.

The calls are the trace's Pallas kernel events named
``paged_decode_attention`` (the kernel's ``name``): the grouped expert
product is a ``tpu_custom_call`` too, 8 a step."""

from benchmarks.harness.trace_reduce import (
    kernel_events,
    live_kv_tokens,
    total_s,
)
from benchmarks.references import nemotron_h, nemotron_h_counts as counts

KERNEL_NAME = "paged_decode_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = [e for e in kernel_events(run.trace) if KERNEL_NAME in e[0]]
    kernel_s = total_s(calls)
    if not calls or kernel_s <= 0:
        return None
    need = counts.paged_attention_call(
        nemotron_h._shape(run.sizes), live_kv_tokens(run), run.slots)
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * len(calls) * least / kernel_s
