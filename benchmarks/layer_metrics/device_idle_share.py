"""1 - union of the device's operation intervals / traced span."""

from benchmarks.harness.trace_reduce import idle_share


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * idle_share(run.trace)
