"""Submit -> the engine's ``Request.admitted_at``, 95th percentile over the
window's requests (both stamps are the engine's own ``time.time()``)."""

from benchmarks.harness.metrics import percentile


def read(run):
    waits = [1e3 * w for w in run.queue_waits.values() if w is not None]
    return percentile(waits, 95) if waits else None
