"""Least time the chip could take for the routed experts' grouped products
of a decode step (the matrices of the experts that got a pair, once, at the
memory bandwidth: ``moe_experts_counts.touched_experts_bytes``) over the time
they took.  Percent.

It reads the same work whatever implements it: the device events inside the
decode-window programs (``XLA Modules`` named ``jit_decode_w<steps>_...``) of
the traced span that are XLA's ``ragged-dot`` custom calls or calls of the
repo's own kernel by its name (``grouped_matmul``).  Their share of those
programs' device time x the step's time (``hybrid_decode_trace``) is their
time a step; the experts touched a step are the program's counter
``moe_experts_touched_sum`` over its decode steps.  ``None`` where the trace
holds neither kind of event.  This file reads the Ling cell
(``moe_experts_roofline.reason``); ``moe_experts_roofline.lfm2.py`` hands
:func:`roofline` the other model's shape."""

from benchmarks.harness.metrics import counter_delta
from benchmarks.harness.trace_reduce import MODULES_LINE, OPS_LINE
from benchmarks.layer_metrics.hybrid_decode_trace import (
    PROGRAM,
    decode_step_ms,
)
from benchmarks.references import moe_experts_counts as counts

TOUCHED = "dstack_serving_moe_experts_touched_sum"
STEPS = "dstack_serving_decode_steps_total"
#: how a grouped product's event name starts: XLA's own, the repo's kernel
PRODUCTS = ("%ragged-dot-none", "%grouped_matmul")


def experts_share(trace):
    """Device time of the grouped products inside the decode-window programs
    over those programs' device time, both inside the traced span."""
    if trace is None or not trace["devices"]:
        return None
    inside_ns = programs_ns = 0
    for dev in trace["devices"]:
        ops = dev["lines"].get(OPS_LINE, [])
        if not ops:
            continue
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        windows = [(max(s, first), min(s + d, last))
                   for name, s, d in dev["lines"].get(MODULES_LINE, [])
                   if PROGRAM.match(name)]
        programs_ns += sum(hi - lo for lo, hi in windows if hi > lo)
        inside_ns += sum(
            d for name, s, d in ops if name.startswith(PRODUCTS)
            and any(lo <= s < hi for lo, hi in windows))
    if not inside_ns or not programs_ns:
        return None
    return inside_ns / programs_ns


def roofline(run, shape: dict):
    share = experts_share(run.trace)
    step_ms = decode_step_ms(run.trace)
    steps = counter_delta(run, STEPS)
    touched = counter_delta(run, TOUCHED)
    if (run.peaks is None or not share or not step_ms or steps <= 0
            or touched <= 0):
        return None
    least_s = (counts.touched_experts_bytes(shape, touched / steps)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (share * step_ms / 1e3)


def read(run):
    from benchmarks.references import ling_hybrid

    return roofline(run, ling_hybrid._shape(run.sizes))
