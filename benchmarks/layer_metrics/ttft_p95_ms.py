"""p95 of due time -> first token over the window's requests."""

from benchmarks.harness.metrics import ttft_p95_ms as read  # noqa: F401
