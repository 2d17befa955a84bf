"""Arithmetic from request timelines to numbers.  Pure Python on lists of
``time.perf_counter()`` stamps, so that it can be checked on a hand-made
timeline."""

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def longest_gap(stamps) -> float:
    """Longest time between two consecutive tokens of one request: what a
    streaming reader sees as a stall.  Tokens come in bursts of a decode
    window, so most gaps are 0 and the longest is a whole window or more."""
    return max((b - a for a, b in zip(stamps, stamps[1:])), default=0.0)


SAME_HANDOVER_S = 0.05


def burst_shares(stamps, t0: float, t1: float):
    """One ``(first index, tokens, share)`` per burst of a request's
    timeline: the share of the burst that was PRODUCED inside [t0, t1).

    The engine hands tokens over in bursts, one per decode window (64 steps,
    seconds apart at today's step times), so counting tokens by the time
    they were received would count whole bursts: a 30 s window holds eight
    to eleven of them, and whether the last one falls inside would move a
    rate by a tenth.  A burst is a run of stamps each less than
    ``SAME_HANDOVER_S`` after the one before (the hand-over loop stamps a
    request's tokens some hundred microseconds apart; two decode windows are
    never that close), and it stands at its first stamp.  A burst received
    at t was produced one step at a time since the request's previous burst
    at t' (the device runs ahead of the host by at most the window it is
    draining), so its tokens are placed evenly on (t', t] and the part of
    that span inside the window is the burst's share.  Both edges are
    treated alike: the caller keeps the load on and keeps taking stamps
    after t1 until every request that was streaming then has had its next
    burst whole.  A request's first burst has no earlier one and stands at
    its own stamp."""
    i, n, prev = 0, len(stamps), None
    while i < n:
        j = i + 1
        while j < n and stamps[j] - stamps[j - 1] < SAME_HANDOVER_S:
            j += 1
        t = stamps[i]
        if prev is None:
            share = 1.0 if t0 <= t < t1 else 0.0
        else:
            share = max(min(t, t1) - max(prev, t0), 0.0) / (t - prev)
        yield i, j - i, share
        prev, i = t, j


def tokens_in_window(timelines, t0: float, t1: float) -> float:
    """Tokens produced inside [t0, t1), over every request."""
    return sum(m * share for stamps in timelines
               for _, m, share in burst_shares(stamps, t0, t1))


def rate(count: float, t0: float, t1: float) -> float:
    return count / (t1 - t0)


def counter_delta(run, name: str) -> float:
    """Growth of one of the program's counters over the window."""
    return run.counters["t1"].get(name, 0.0) - run.counters["t0"].get(name, 0.0)


def window_flops(run) -> float:
    """FLOPs the model needs for the tokens produced inside the window
    (``burst_shares``): a request's first token carries its whole prompt,
    every later one a decode step at its own context length."""
    from benchmarks.harness.flops_bytes import sequence_flops, token_flops

    total = 0.0
    for r in run.all_requests:
        n = len(r.prompt)
        for i, m, share in burst_shares(r.stamps, run.t0, run.t1):
            if share:
                total += share * sum(
                    sequence_flops(run.sizes, n, 1) if j == 0
                    else token_flops(run.sizes, n + j)
                    for j in range(i, i + m))
    return total


def mfu_percent(run):
    """The whole window's share of the chips' bf16 peak."""
    if run.peaks is None:
        return None
    flops = window_flops(run)
    if not flops:
        return None
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (run.t1 - run.t0) / peak


def ttft_p95_ms(run):
    """95th percentile, over the window's requests, of due time -> first
    token (the generator's lateness and the queue both count)."""
    if not run.requests:
        return None
    return percentile([1e3 * (r.stamps[0] - r.due) for r in run.requests], 95)


def gap_p95_ms(run):
    """Each request's LONGEST gap between consecutive tokens, 95th
    percentile over the window's requests.  Tokens arrive in bursts of a
    decode window, so the percentile of all gaps would read 0 until it
    reads a whole window; the longest gap is the stall a streaming reader
    sees."""
    gaps = [1e3 * longest_gap(r.stamps) for r in run.requests
            if len(r.stamps) > 1]
    return percentile(gaps, 95) if gaps else None
