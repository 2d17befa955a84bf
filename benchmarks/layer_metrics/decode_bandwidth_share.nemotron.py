"""Bytes a decode step has to move / the step's device time / the chip's
memory bandwidth.  The bytes (``nemotron_h_counts.decode_step_bytes``): every
matrix outside the routed experts and the head once, the matrices of the
experts that got a pair (``moe_experts_touched_sum`` over the window's
steps), the state-space states (float32) and convolution tails of the
decoding slots read and written, the K and V rows of the tokens they hold in
the one attention layer."""

from benchmarks.harness.metrics import counter_delta
from benchmarks.harness.trace_reduce import live_kv_tokens
from benchmarks.layer_metrics.hybrid_decode_trace import decode_step_ms
from benchmarks.references import nemotron_h, nemotron_h_counts as counts

TOUCHED = "dstack_serving_moe_experts_touched_sum"
STEPS = "dstack_serving_decode_steps_total"
OCCUPANCY = "dstack_serving_batch_occupancy_%s{phase=decode}"


def read(run):
    step_ms = decode_step_ms(run.trace)
    steps = counter_delta(run, STEPS)
    windows = counter_delta(run, OCCUPANCY % "count")
    if run.peaks is None or not step_ms or steps <= 0 or windows <= 0:
        return None
    shape = nemotron_h._shape(run.sizes)
    live_slots = run.slots * counter_delta(run, OCCUPANCY % "sum") / windows
    need = counts.decode_step_bytes(
        shape, live_slots, live_kv_tokens(run),
        counter_delta(run, TOUCHED) / steps)
    return 100.0 * need / (step_ms / 1e3) / run.peaks["hbm_bytes_per_s"]
