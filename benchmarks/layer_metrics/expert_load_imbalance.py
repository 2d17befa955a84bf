"""Largest number of pairs one held expert got in an expert layer-step / the
mean over the held experts, both summed over the decode windows drained in
the window (``moe_expert_load_max_sum`` / ``moe_expert_load_mean_sum``).
1 is even; the grouped product's time follows the fullest expert's rows."""

from benchmarks.harness.metrics import counter_delta

MAX = "dstack_serving_moe_expert_load_max_sum"
MEAN = "dstack_serving_moe_expert_load_mean_sum"


def read(run):
    mean = counter_delta(run, MEAN)
    return counter_delta(run, MAX) / mean if mean > 0 else None
