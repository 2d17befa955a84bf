"""The one traffic generator.  A traffic mix is a file of parameters under
``benchmarks/traffic``; a cell adds its load (a rate or a number of
clients).  Every run gets the SAME set of lengths and of gaps between
arrivals: the distribution's quantiles on an even grid.  In a closed loop
the grid is laid once per ROUND (one request of every client), so that
whichever requests a window happens to hold, it holds nearly the same mix of
long and short ones.  Their order is a permutation drawn from the traffic
file's ``order_seed`` where it has one, and from ``--seed`` where it has
none; token ids (and the weights) always come from ``--seed``.  A mix fixes
its order where the order IS the work: a window that holds a few dozen
requests reads a different rate for every permutation of them.

The generator knows nothing of the program: it is handed ``submit(rec,
on_complete)`` and learns of a completion when the system calls
``on_complete``.
"""

import dataclasses
import math
import statistics
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Rec:
    """One planned request and, later, its timeline (perf_counter stamps)."""
    index: int
    prompt: np.ndarray
    max_new: int
    client: Optional[int] = None
    due_rel: Optional[float] = None      # open loop: seconds after start
    due: Optional[float] = None
    submitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    served: List[int] = dataclasses.field(default_factory=list)
    handle: Any = None                   # the program's own request object

    @property
    def complete(self) -> bool:
        return len(self.served) >= self.max_new


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths on the even quantile grid of a named distribution."""
    u = _grid(n)
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"]) - 0.5
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(v)) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def arrival_gaps(arrivals: dict, rate: float, n: int) -> np.ndarray:
    """``n`` gaps between arrivals with mean 1/rate: ``poisson`` gives the
    exponential's quantiles, ``gamma`` (with ``cv``) a burstier set."""
    u = _grid(n)
    kind = arrivals.get("process", "poisson")
    if kind == "poisson":
        gaps = -np.log1p(-u)
    elif kind == "gamma":
        from scipy.stats import gamma

        gaps = gamma.ppf(u, 1.0 / float(arrivals["cv"]) ** 2)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return gaps / gaps.mean() / rate


def plan(traffic: dict, load: dict, vocab: int, seed: int,
         horizon_s: float) -> List[Rec]:
    """The requests of one run, in order of submission (open loop) or dealt
    to the clients in turn (closed loop: client = index % clients)."""
    rng = np.random.default_rng([int(seed), 0x10AD])
    order = np.random.default_rng(
        [int(traffic.get("order_seed", seed)), 0x0DE5])
    if traffic["kind"] == "open":
        block = n = int(math.ceil(load["rate_per_s"] * horizon_s * 1.15)) + 8
    elif traffic["kind"] == "closed":
        block = int(load["clients"])
        n = block * int(traffic["requests_per_client"])
    else:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")

    def lengths(dist):
        # whole blocks, each the quantile grid in an order of its own
        return np.concatenate([order.permutation(length_quantiles(dist, block))
                               for _ in range(n // block)])

    prompts, outputs = lengths(traffic["prompt_len"]), lengths(
        traffic["output_len"])
    ids = rng.integers(0, vocab, size=int(prompts.sum()), dtype=np.int32)
    share = float(traffic.get("shared_prefix_share", 0.0))
    common = rng.integers(0, vocab, size=int(prompts.max()), dtype=np.int32)
    recs, at = [], 0
    for i in range(n):
        p = ids[at:at + prompts[i]].copy()
        at += prompts[i]
        k = int(share * len(p))
        p[:k] = common[:k]
        recs.append(Rec(index=i, prompt=p, max_new=int(outputs[i])))
    if traffic["kind"] == "open":
        gaps = order.permutation(arrival_gaps(traffic.get("arrivals", {}),
                                              load["rate_per_s"], n))
        for rec, due in zip(recs, np.cumsum(gaps)):
            rec.due_rel = float(due)
    else:
        clients = int(load["clients"])
        for rec in recs:
            rec.client = rec.index % clients
        if traffic.get("stagger_first", False):
            # the clients' first requests end at evenly spread times, so
            # that the window opens on slots at different points of their
            # requests, not on sixteen prompts prefilling together
            for rec in recs[:clients]:
                rec.max_new = max(8, round(rec.max_new * (rec.client + 1)
                                           / clients))
    return recs


def drive(recs: List[Rec], traffic: dict, load: dict,
          submit: Callable[[Rec, Callable[[Rec], None]], None],
          start: float, stop: float, failed: Callable[[Rec], bool],
          hold: Callable[[List[Rec]], bool] = lambda sent: False) -> dict:
    """Offer the load from ``start`` until ``stop`` (perf_counter times), and
    on after ``stop`` for as long as ``hold(sent)`` says so: the caller
    collects the window's last bursts under the same load.  ``submit(rec,
    on_complete)`` hands a request over; the system calls ``on_complete(rec)``
    when the request's last token is out.  Returns ``{"submitted",
    "lateness_s": [...]}``.  ``failed(rec)`` tells whether a request ended
    without all its tokens (its client then goes on to its next request, as
    one that got an error back would).

    A closed loop's client sends its next request INSIDE ``on_complete``, on
    the thread that handed over the last token: sent from this thread a few
    hundred microseconds later, it would race the system's next look at its
    queue, and the loser of that race waits a whole decode window."""
    sent = []
    lateness = []

    def over() -> bool:
        return time.perf_counter() >= stop and not hold(sent)

    if traffic["kind"] == "open":
        for rec in recs:
            due = start + rec.due_rel
            while not over() and time.perf_counter() < due:
                time.sleep(min(max(due - time.perf_counter(), 0.0),
                               0.02 if due >= stop else 3600.0))
            if over():
                break
            rec.due = due
            rec.submitted = time.perf_counter()
            lateness.append(rec.submitted - due)
            submit(rec, lambda done: None)
            sent.append(rec)
        else:
            raise RuntimeError("the plan ran out of requests before the "
                               "window closed")
        return {"submitted": sent, "lateness_s": lateness}

    clients = int(load["clients"])
    waiting = [[r for r in recs if r.client == c] for c in range(clients)]
    current = {}
    lock = threading.Lock()
    state = {"open": True, "error": None}

    def send_next(c):
        with lock:
            if not state["open"]:
                return
            if not waiting[c]:
                state["error"] = (f"client {c} ran out of planned requests; "
                                  f"raise requests_per_client")
                return
            rec = waiting[c].pop(0)
            rec.due = rec.submitted = time.perf_counter()
            current[c] = rec
            sent.append(rec)
        submit(rec, lambda done: send_next(done.client))

    for c in range(clients):
        send_next(c)
    while not over():
        time.sleep(min(max(stop - time.perf_counter(), 0.02), 0.05))
        if state["error"]:
            raise RuntimeError(state["error"])
        with lock:
            ended = [c for c, rec in current.items()
                     if not rec.complete and failed(rec)]
        for c in ended:
            send_next(c)
    with lock:
        state["open"] = False
    return {"submitted": sent, "lateness_s": lateness}
