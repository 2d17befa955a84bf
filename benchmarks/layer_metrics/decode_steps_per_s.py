"""Decode steps of the windows handed over in the window / window (the
engine counts a window's steps where it drains it: ``decode_steps_total``).
Whole windows: at 64 steps a window the reading moves in steps of 64/30."""

from benchmarks.harness.metrics import counter_delta

NAME = "dstack_serving_decode_steps_total"


def read(run):
    if NAME not in run.counters["t1"]:
        return None
    return counter_delta(run, NAME) / (run.t1 - run.t0)
