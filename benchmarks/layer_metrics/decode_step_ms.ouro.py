"""Device time of the decode-window programs (``jit_decode_w<steps>_*``) /
the decode steps they ran (``looped_decode_trace.py``): a step of the looped
decoder is ``total_ut_steps`` x layers kernel calls, where the generic reader
counts ``sizes.layers`` and would read 4 x off."""

from benchmarks.layer_metrics.looped_decode_trace import decode_step_ms
from benchmarks.references import ouro_looped


def read(run):
    passes, _ = ouro_looped._loop()
    return decode_step_ms(run.trace, passes * run.sizes.layers)
