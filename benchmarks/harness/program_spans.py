"""The program's own spans and names in a reduced trace, and arithmetic on
interval lists.  ``InferenceEngine`` records ``jax.profiler.TraceAnnotation``
spans named ``engine.*`` around the phases of its scheduling step; they land
on the host lines of the profiler's trace, on the device trace's clock.  A
program without them (an older commit) gives empty lists and the readers
return ``None``."""

from benchmarks.harness.trace_reduce import (
    MODULES_LINE,
    _busy,
    traced_span_ns,
    union,
)

SPAN_PREFIX = "engine."


def host_spans(trace, *names: str) -> list:
    """Disjoint, sorted ``[start, end]`` of the host events called any of
    ``names``, over every thread.  The profiler keeps a span only if it
    both began and ended inside the traced window, so a long span is best
    read together with the shorter ones it encloses."""
    return union((s, s + d) for events in trace["host"].values()
                 for n, s, d in events if n in names)


def has_program_spans(trace) -> bool:
    return any(n.startswith(SPAN_PREFIX)
               for events in trace["host"].values() for n, _, _ in events)


def complement(intervals, lo: int, hi: int) -> list:
    """What ``[lo, hi]`` holds outside the disjoint, sorted ``intervals``."""
    out, at = [], lo
    for start, end in intervals:
        if start > at:
            out.append([at, min(start, hi)])
        at = max(at, end)
        if at >= hi:
            break
    if at < hi:
        out.append([at, hi])
    return [iv for iv in out if iv[1] > iv[0]]


def overlap_ns(a, b) -> int:
    """Total length of the intersection of two disjoint, sorted lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(trace, device) -> list:
    """Where no operation ran on ``device`` inside the traced span: the
    complement of the union ``device_idle_share`` is one minus."""
    lo, hi = traced_span_ns(trace)
    return complement(_busy(device), lo, hi)


def idle_share_inside(trace, *span_names: str):
    """Device-idle time that falls inside the host spans ``span_names``,
    over the traced span, averaged over the chips: a part of the idle
    share.  0 when the program records spans and none of these is there."""
    if not trace["devices"] or not has_program_spans(trace):
        return None
    lo, hi = traced_span_ns(trace)
    spans = host_spans(trace, *span_names)
    inside = [overlap_ns(idle_intervals(trace, d), spans)
              for d in trace["devices"]]
    return sum(inside) / len(inside) / (hi - lo)


def module_share(trace, prefix: str):
    """Device time of the compiled programs whose name starts ``prefix``
    over the traced span, averaged over the chips; ``None`` when no program
    is so named."""
    if not trace["devices"]:
        return None
    lo, hi = traced_span_ns(trace)
    per_chip = [[(s, s + d) for n, s, d in dev["lines"].get(MODULES_LINE, [])
                 if n.startswith(prefix)] for dev in trace["devices"]]
    if not any(per_chip):
        return None
    busy = [sum(e - s for s, e in union(ivs)) for ivs in per_chip]
    return sum(busy) / len(busy) / (hi - lo)
