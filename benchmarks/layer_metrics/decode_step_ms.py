"""Device time of the decode-window programs / decode steps they ran."""

from benchmarks.harness.trace_reduce import decode_step_ms


def read(run):
    return None if run.trace is None else decode_step_ms(run.trace, run.sizes)
