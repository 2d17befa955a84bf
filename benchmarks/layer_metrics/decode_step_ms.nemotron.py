"""Device time of the decode-window programs (``jit_decode_*``) / the decode
steps they ran (``hybrid_decode_trace.py``: by the programs' names and by
what a cut window shows, so it counts no layer and no kernel call)."""

from benchmarks.layer_metrics.hybrid_decode_trace import decode_step_ms


def read(run):
    return decode_step_ms(run.trace)
