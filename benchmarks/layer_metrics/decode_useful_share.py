"""Tokens the decode windows handed over / slot-steps the device computed
for them (steps x all slots: a step costs the same with one slot live or
every one).  What is missing from 100% is slots that were empty, chunking or
past their request's end while a window ran."""

from benchmarks.harness.metrics import counter_delta

TOKENS = "dstack_serving_decode_tokens_total"
SLOT_STEPS = "dstack_serving_decode_slot_steps_total"


def read(run):
    slot_steps = counter_delta(run, SLOT_STEPS)
    if slot_steps <= 0:
        return None
    return 100.0 * counter_delta(run, TOKENS) / slot_steps
