"""Device time of the decode-window programs (``jit_decode_*``) / the decode
steps they ran (``hybrid_decode_trace.py``)."""

from benchmarks.layer_metrics.hybrid_decode_trace import decode_step_ms


def read(run):
    return decode_step_ms(run.trace)
