"""p95 over the window's requests of each one's longest gap between tokens."""

from benchmarks.harness.metrics import gap_p95_ms as read  # noqa: F401
