"""``device_idle_share`` split by what the host was doing: the sum its terms
make, and the arithmetic the readers ``idle_unattributed`` and
``idle_inside_programs`` share (``idle_in_chunks`` and ``idle_in_handover``
read their spans as ``idle_in_admission`` does).

The engine's phases are sibling ``engine.*`` host spans on one thread
(``admit`` holds ``prefill``; ``prefill`` and ``chunk`` hold ``first_token``;
any may hold ``build_program``), so the idle time under disjoint sets of them
adds up: in a traced run

    device_idle_share = idle_in_admission + idle_in_chunks + idle_in_handover
                        + idle_unattributed
                        (+ idle under engine.wait_for_work, under a
                        build_program outside the others and under a
                        first_token whose prefill span an edge of the
                        trace dropped: 0 in a saturated closed loop after
                        warm-up, but for that last)

to the nanosecond.  ``idle_inside_programs`` is an overlay, not a term."""

from benchmarks.harness.program_spans import (
    SPAN_PREFIX,
    complement,
    host_spans,
    idle_intervals,
    overlap_ns,
)
from benchmarks.harness.trace_reduce import traced_span_ns

def idle_share_within(trace, intervals_of) -> float:
    """Device-idle time inside the disjoint, sorted intervals
    ``intervals_of(device)`` over the traced span, averaged over the chips."""
    lo, hi = traced_span_ns(trace)
    inside = [overlap_ns(idle_intervals(trace, d), intervals_of(d))
              for d in trace["devices"]]
    return sum(inside) / len(inside) / (hi - lo)


def outside_every_phase(trace) -> list:
    """Where, in the traced span, no ``engine.*`` span of any name is open."""
    names = {n for events in trace["host"].values() for n, _, _ in events
             if n.startswith(SPAN_PREFIX)}
    lo, hi = traced_span_ns(trace)
    return complement(host_spans(trace, *names), lo, hi)
