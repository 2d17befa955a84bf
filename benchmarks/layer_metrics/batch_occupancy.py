"""Decoding slots / slots, averaged over the decode windows dispatched in
the measured window (``EngineTelemetry.record_window``)."""

from benchmarks.harness.metrics import counter_delta

NAME = "dstack_serving_batch_occupancy_%s{phase=decode}"


def read(run):
    count = counter_delta(run, NAME % "count")
    if count <= 0:
        return None
    return 100.0 * counter_delta(run, NAME % "sum") / count
