"""Bytes a decode step of the looped decoder has to move / the step's device
time / the chip's memory bandwidth.  The bytes
(``ouro_looped_counts.decode_step_bytes``): the layers' matrices once a pass,
the head once, the live tokens' keys and values over all cache layers."""

from benchmarks.harness.trace_reduce import live_kv_tokens
from benchmarks.layer_metrics.looped_decode_trace import decode_step_ms
from benchmarks.references import ouro_looped, ouro_looped_counts as counts


def read(run):
    passes, _ = ouro_looped._loop()
    step_ms = decode_step_ms(run.trace, passes * run.sizes.layers)
    if run.peaks is None or not step_ms:
        return None
    need = counts.decode_step_bytes(run.sizes, passes, live_kv_tokens(run))
    return 100.0 * need / (step_ms / 1e3) / run.peaks["hbm_bytes_per_s"]
