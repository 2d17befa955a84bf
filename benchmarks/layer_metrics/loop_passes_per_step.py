"""Passes over the layer stack a decode step ran:
``loop_passes_total{phase=decode}`` (counted inside the decode program,
added where a window is drained) / ``decode_steps_total`` (added at the same
place).  A looped decoder of T passes reads T as long as none is skipped; a
program without the counter (a plain decoder, the parent commit) reads
nothing."""

from benchmarks.harness.metrics import counter_delta

PASSES = "dstack_serving_loop_passes_total{phase=decode}"
STEPS = "dstack_serving_decode_steps_total"


def read(run):
    passes, steps = counter_delta(run, PASSES), counter_delta(run, STEPS)
    return passes / steps if passes > 0 and steps > 0 else None
