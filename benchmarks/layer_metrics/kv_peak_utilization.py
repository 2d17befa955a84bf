"""Highest share of the KV pool in use since the engine started, read at the
window's end (the engine records it where the pool grows)."""

NAME = "dstack_serving_kv_utilization_peak"


def read(run):
    peak = run.counters["t1"].get(NAME)
    return None if peak is None else 100.0 * peak
