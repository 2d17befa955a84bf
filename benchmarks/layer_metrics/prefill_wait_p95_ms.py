"""Slot admission -> first token (the engine's ``engine.prefill`` request
span: ``first_token_at - admitted_at``), 95th percentile over the window's
requests: the prefill program, or the wait in the chunk queue."""

from benchmarks.harness.metrics import percentile


def read(run):
    waits = []
    for r in run.requests:
        h = getattr(r, "handle", None)
        admitted = getattr(h, "admitted_at", None)
        first = getattr(h, "first_token_at", None)
        if admitted is not None and first is not None:
            waits.append(1e3 * max(first - admitted, 0.0))
    return percentile(waits, 95) if waits else None
