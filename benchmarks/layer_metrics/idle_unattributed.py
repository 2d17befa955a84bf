"""Device-idle time under NO ``engine.*`` span / traced span: host work of
the engine's thread outside every phase, and what a span hid that the
profiler dropped because it crossed an edge of the trace.  The remainder of
the sum in ``idle_split.py``; ``None`` when the program records no spans."""

from benchmarks.harness.program_spans import has_program_spans
from benchmarks.layer_metrics.idle_split import (
    idle_share_within,
    outside_every_phase,
)


def read(run):
    if (run.trace is None or not run.trace["devices"]
            or not has_program_spans(run.trace)):
        return None
    outside = outside_every_phase(run.trace)
    return 100.0 * idle_share_within(run.trace, lambda device: outside)
