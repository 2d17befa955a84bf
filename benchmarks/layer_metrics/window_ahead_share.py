"""Decode windows enqueued before their predecessor was drained / all decode
windows dispatched in the window (``windows_dispatched_ahead_total`` over
``batch_occupancy_count{phase=decode}``): how much of the window chain is
pipelined.  ``None`` for a program without the counter, or with no window."""

from benchmarks.harness.metrics import counter_delta

AHEAD = "dstack_serving_windows_dispatched_ahead_total"
WINDOWS = "dstack_serving_batch_occupancy_count{phase=decode}"


def read(run):
    if AHEAD not in run.counters["t1"]:
        return None
    windows = counter_delta(run, WINDOWS)
    if windows <= 0:
        return None
    return 100.0 * counter_delta(run, AHEAD) / windows
