"""The hybrid decoder's decode-window programs in a reduced trace: shared by
the readers ``decode_step_ms.reason`` and ``decode_bandwidth_share.reason``
(not a reader itself).

The programs are the ``XLA Modules`` named ``jit_decode_w<steps>_...``
(``_named_jit``): a window of ``<steps>`` decode steps.  One that lies whole
inside the traced span ran the steps its name carries.  The traced span is
hardly longer than a window, so most are cut by an edge: such a one counts
the part of it inside the span and the steps it shows there.  Every
instruction of the window's loop shows once a step, whatever the step is
made of, so the steps shown are how often the instructions inside the part
show: the median over those that show twice or more, and the mean over the
instructions within one of it (a step cut in two shows part of its
instructions).  A trace without such modules (the parent commit, another
model, the CPU) gives ``None``."""

import re
from collections import Counter
from statistics import mean, median

from benchmarks.harness.trace_reduce import MODULES_LINE, OPS_LINE

PROGRAM = re.compile(r"jit_decode_w(\d+)_")


def _steps_shown(ops, lo, hi):
    """Decode steps that the operations starting in ``[lo, hi)`` show."""
    shows = [c for c in Counter(n for n, s, _ in ops if lo <= s < hi).values()
             if c >= 2]
    if not shows:
        return 0.0
    middle = median(shows)
    return mean(c for c in shows if abs(c - middle) <= 1)


def decode_step_ms(trace):
    """Device milliseconds of the decode-window programs per decode step."""
    if trace is None or not trace["devices"]:
        return None
    busy_ns = steps = 0.0
    for dev in trace["devices"]:
        ops = dev["lines"].get(OPS_LINE, [])
        if not ops:
            continue
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        for name, s, d in dev["lines"].get(MODULES_LINE, []):
            program = PROGRAM.match(name)
            if not program:
                continue
            if first < s and s + d < last:
                ran = float(program.group(1))
            else:
                s, d = max(s, first), min(s + d, last) - max(s, first)
                ran = _steps_shown(ops, s, s + d)
            if ran:
                busy_ns += d
                steps += ran
    return busy_ns / 1e6 / steps if steps else None
