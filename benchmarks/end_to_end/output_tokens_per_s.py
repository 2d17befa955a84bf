"""Every token any request received inside the window, over the window."""

from benchmarks.harness.metrics import rate, tokens_in_window


def read(run):
    n = tokens_in_window((r.stamps for r in run.all_requests), run.t0, run.t1)
    return rate(n, run.t0, run.t1) if n else None
