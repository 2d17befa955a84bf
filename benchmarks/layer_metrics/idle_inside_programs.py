"""Device-idle time that lies inside an ``XLA Modules`` event / traced span:
gaps between the operations of a program that is running.  An overlay on
``device_idle_share``, not a term of its split (``idle_split.py``): it says
how much of the idle share no scheduler can take away.  ``None`` where the
trace names no program."""

from benchmarks.harness.trace_reduce import MODULES_LINE, union
from benchmarks.layer_metrics.idle_split import idle_share_within


def _programs(device) -> list:
    return union((s, s + d) for _, s, d in device["lines"].get(
        MODULES_LINE, []))


def read(run):
    if run.trace is None or not any(
            _programs(d) for d in run.trace["devices"]):
        return None
    return 100.0 * idle_share_within(run.trace, _programs)
