"""Token-expert pairs of the decode windows that fell on experts this chip
holds / all their pairs (``moe_pairs_total{where=held|absent}``, counted
where a window is drained from the sums its program returned).  Even
routing over 512 experts with 128 held reads 25%."""

from benchmarks.harness.metrics import counter_delta

NAME = "dstack_serving_moe_pairs_total{where=%s}"


def read(run):
    held = counter_delta(run, NAME % "held")
    total = held + counter_delta(run, NAME % "absent")
    return 100.0 * held / total if total > 0 else None
