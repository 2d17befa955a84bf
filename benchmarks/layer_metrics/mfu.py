"""Model FLOPs of everything processed in the window / window / bf16 peak."""

from benchmarks.harness.metrics import mfu_percent as read  # noqa: F401
