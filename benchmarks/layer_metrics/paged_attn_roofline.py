"""Roofline share of the paged decode-attention kernel calls in the trace."""

from benchmarks.harness.trace_reduce import paged_attn_roofline as read  # noqa: F401
