"""From the profiler's trace to numbers.  ``load`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote into plain lists of ``(name, start_ns, dur_ns)``;
everything else is arithmetic on those lists, so it can be checked on a
hand-made trace.

A trace is ``{"devices": [{"name", "lines": {line: [event, ...]}}, ...],
"host": {line: [event, ...]}}``.  On a TPU each chip is a plane
``/device:TPU:n`` whose line ``XLA Ops`` holds every operation that ran on
it and ``XLA Modules`` every compiled program (``jit_<function>``)."""

import bisect
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir, chips: int) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    devices, host = [], {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if plane.name.startswith("/device:TPU:"):
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for name, events in lines.items():
                host.setdefault(name, []).extend(events)
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices[:chips], "host": host}


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns them disjoint and sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _ops(device) -> list:
    lines = device["lines"]
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def _busy(device) -> list:
    return union((s, s + d) for _, s, d in _ops(device))


def traced_span_ns(trace) -> tuple:
    """First start and last end over every event of every plane: the host's
    own events bracket the device's, so idle time at either edge counts."""
    starts, ends = [], []
    for lines in [d["lines"] for d in trace["devices"]] + [trace["host"]]:
        for events in lines.values():
            for _, s, d in events:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no event")
    return min(starts), max(ends)


def busy_and_window(trace) -> dict:
    """Seconds in which an operation ran on the device, averaged over the
    chips, and the length of the traced window."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    lo, hi = traced_span_ns(trace)
    busy = [sum(e - s for s, e in _busy(d)) for d in trace["devices"]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}


def idle_share(trace) -> float:
    b = busy_and_window(trace)
    return 1.0 - b["busy_s"] / b["window_s"]


def events_named(trace, line: str, contains: str) -> list:
    """Events of one device line, over all chips, whose name holds
    ``contains``."""
    return [e for d in trace["devices"] for e in d["lines"].get(line, [])
            if contains in e[0]]


def total_s(events) -> float:
    return sum(d for _, _, d in events) / 1e9


#: operations that only hold other operations (a loop and its body both
#: appear on the line): left out of the list of the costliest operations
CONTAINERS = ("%while", "%conditional", "%call")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def short_name(op: str) -> str:
    """The trace names an operation by its whole HLO line; keep the result's
    name, its shape and the opcode: ``%copy.108 bf16[768,32,2048] copy``."""
    lhs, _, rhs = op.partition(" = ")
    if not rhs:
        return op[:120]
    shape, _, rest = rhs.partition(" ")
    if shape.startswith("("):           # a tuple result: keep its first part
        shape = shape[:shape.index("]") + 1] + ",..)"
        rest = rhs[rhs.index(") ") + 2:] if ") " in rhs else rest
    shape = shape.split("{")[0]
    return f"{lhs} {shape} {rest.split('(')[0]}"[:120]


def kernel_events(trace) -> list:
    """Every Pallas kernel call on the devices' operation lines."""
    return events_named(trace, OPS_LINE, KERNEL_MARK)


def decode_step_ms(trace, sizes):
    """Device time of the decode-window programs over the decode steps they
    ran.  The program names neither: a decode program is a module inside
    which the paged-attention kernel runs (prefill attends in plain XLA),
    and one decode step calls that kernel once per layer."""
    calls = kernel_events(trace)
    if not calls:
        return None
    busy_ns = 0
    for dev in trace["devices"]:
        starts = sorted(s for name, s, _ in dev["lines"].get(OPS_LINE, [])
                        if KERNEL_MARK in name)
        for _, s, d in dev["lines"].get(MODULES_LINE, []):
            i = bisect.bisect_left(starts, s)
            if i < len(starts) and starts[i] < s + d:
                busy_ns += d
    steps = len(calls) / sizes.layers
    return busy_ns / 1e6 / steps


def live_kv_tokens(run, samples: int = 64) -> float:
    """Mean, over the traced span, of the tokens held by decoding requests
    (prompt plus the tokens received so far, which trail the device by up to
    a decode window: the count errs low, so a roofline share from it does
    too)."""
    lo, hi = run.trace_span
    total = 0.0
    for i in range(samples):
        t = lo + (hi - lo) * (i + 0.5) / samples
        for r in run.all_requests:
            if r.stamps and r.stamps[0] <= t < r.stamps[-1]:
                total += len(r.prompt) + bisect.bisect_right(r.stamps, t)
    return total / samples


def paged_attn_roofline(run):
    """Least time the chip could take for the paged-attention calls of the
    traced span (the larger of bytes / bandwidth and FLOPs / peak, from the
    live keys and values) over the time they took.  Percent."""
    from benchmarks.harness.flops_bytes import paged_attention_call

    if run.trace is None or run.peaks is None:
        return None
    calls = kernel_events(run.trace)
    kernel_s = total_s(calls)
    if not calls or kernel_s <= 0:
        return None
    need = paged_attention_call(run.sizes, live_kv_tokens(run), run.slots)
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * len(calls) * least / kernel_s


def _gap_label(host, start, end) -> str:
    """What the host was doing in a gap: the host event that overlaps most
    of it.  The program records no spans of its own, so this is whatever the
    runtime's own tracing names (a transfer, an execute call), by thread."""
    best, best_overlap = "host: nothing recorded", 0
    for thread, events in host.items():
        for name, s, d in events:
            overlap = min(end, s + d) - max(start, s)
            if overlap > best_overlap:
                best, best_overlap = f"{thread}: {name}", overlap
    return best


def breakdown(trace, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle gaps,
    each as at most ``top`` ``[name, seconds]`` pairs, over the first chip's
    timeline (the chips of one program run in step)."""
    if not trace["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace["devices"][0]
    by_op = {}
    for name, _, d in _ops(dev):
        if not name.startswith(CONTAINERS):
            by_op[short_name(name)] = by_op.get(short_name(name), 0) + d
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = _busy(dev)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:top]
    return {
        "device_ops": [[n, d / 1e9] for n, d in ops],
        "idle_gaps": [[_gap_label(trace["host"], s, e), g / 1e9]
                      for g, s, e in gaps],
    }
