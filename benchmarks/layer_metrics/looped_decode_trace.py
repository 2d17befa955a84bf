"""The looped decoder's decode-window programs in a reduced trace: shared by
the readers ``decode_step_ms.ouro`` and ``decode_bandwidth_share.ouro`` (not
a reader itself).

The programs are the ``XLA Modules`` named ``jit_decode_w<steps>_...``.  A
window of this model outlasts the traced span (64 steps of some 50 ms
against 3 s), so every one is cut by an edge and the steps of its name are
not the steps the span shows.  What shows them is the paged-attention
kernel: one call a layer a pass, so a step is ``passes`` x ``layers`` calls,
and the part of a program that lies inside the span ran the calls that
start there over that many steps.  (``hybrid_decode_trace.py`` counts how
often an instruction shows, which holds for a step without loops inside it:
here the layers' instructions show 192 times a step, the passes' 4 times.)
A trace without such programs or without the kernel gives ``None``."""

import bisect
import re

from benchmarks.harness.trace_reduce import KERNEL_MARK, MODULES_LINE, OPS_LINE

PROGRAM = re.compile(r"jit_decode_w(\d+)_")


def decode_step_ms(trace, calls_per_step: int):
    """Device milliseconds of the decode-window programs per decode step."""
    if trace is None or not trace["devices"]:
        return None
    busy_ns = calls = 0
    for dev in trace["devices"]:
        ops = dev["lines"].get(OPS_LINE, [])
        if not ops:
            continue
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        kernels = sorted(s for name, s, _ in ops if KERNEL_MARK in name)
        for name, s, d in dev["lines"].get(MODULES_LINE, []):
            if not PROGRAM.match(name):
                continue
            lo, hi = max(s, first), min(s + d, last)
            inside = (bisect.bisect_left(kernels, hi)
                      - bisect.bisect_left(kernels, lo))
            if hi > lo and inside:
                busy_ns += hi - lo
                calls += inside
    return busy_ns / 1e6 * calls_per_step / calls if calls else None
