"""Serving-engine telemetry: the metric set the gateway autoscaler and
SLO dashboards key on.

One ``EngineTelemetry`` instance per ``InferenceEngine``; all record_*
methods are called from the engine's scheduler thread only (the same
thread that runs ``step()``), so nothing here locks.  The HTTP side reads
through ``prometheus_samples()`` / ``stats()`` which only snapshot.

Metric names (all prefixed ``dstack_serving_``; scraped by the PR-1
server scraper through the auto-declared ``metrics:`` block and
republished with project/run/job/replica labels):

- ``queue_wait_seconds``    histogram — submit -> slot admission
- ``ttft_seconds``          histogram — submit -> first emitted token
- ``inter_token_seconds``   histogram — decode-window wall time / tokens
- ``e2e_seconds``           histogram — submit -> finish
- ``batch_occupancy{phase}``histogram — fraction of capacity used per
  prefill (real tokens / padded bucket) and per decode window
  (decoding slots / batch_size)
- ``kv_utilization``        gauge — KV blocks (paged) or cache rows
  (dense) in use, fraction of capacity; ``kv_utilization_peak`` is its
  highest value since start (what ``total_kv_blocks`` is sized against)
- ``active_slots`` / ``queue_depth`` gauges
- ``prefill_backlog_tokens`` gauge — prompt tokens still awaiting a
  chunked-prefill dispatch (the signal a router uses to avoid piling
  long prompts onto one replica)
- ``requests_total{outcome}``, ``prefill_tokens_total``,
  ``decode_tokens_total``, ``preemptions_total{reason}`` counters
- ``decode_steps_total`` / ``decode_slot_steps_total`` counters — decode
  steps of the windows handed over so far, and the same times
  ``batch_size``: what the device computed for them (a step costs the
  same with one slot live or all of them).
  ``decode_tokens_total`` over ``decode_slot_steps_total`` is the share of
  that work that became a token; steps over
  ``batch_occupancy_count{phase=decode}`` is the mean window size
- ``engine_phase_seconds_total{phase}`` / ``engine_phases_total{phase}``
  counters — the engine thread's time by the phase of its loop
  (``PHASES``: the ``engine.<phase>`` profiler spans of
  ``serving/engine.py``, one count and its seconds a span, on
  ``time.perf_counter``).  SELF time: a phase opened inside another
  (``prefill`` in ``admit``, ``first_token`` in ``prefill`` or ``chunk``,
  ``build_program`` in any) pauses its parent, so no second is counted
  twice and the phases' sum is at most the thread's wall time.  ``pull``
  and ``first_token`` hold every device->host transfer of the thread (the
  wait for a decode window; for the programs of an admission pass, or of
  the chunked prompts a step completed: ONE ``first_token`` a pass, so its
  count is pulls, not requests), ``wait_for_work`` the idle loop: the rest
  is the host's own work.  A phase still open when the counters are read
  has not been added yet
- ``engine_slot_update_programs_total`` / ``engine_slot_updates_total``
  counters — programs that wrote the slots' device state (length,
  activity, last token: one at the end of an admission pass, one at the
  end of a drain, one before a window where something is still pending)
  and slots they wrote: their ratio is how many activations and releases
  one program stands for
- ``windows_dispatched_ahead_total`` counter — decode windows enqueued
  before their predecessor's tokens were pulled (over
  ``batch_occupancy_count{phase=decode}``, all windows: the share of the
  window chain that is pipelined); ``window_chain_breaks_total{reason}``
  counts the scheduling steps that had a window in flight and dispatched
  none behind it: ``admission`` (a waiting request could take a free
  slot), ``prompt_completed`` (the step's chunks ended a prompt, which
  joins the next window), ``drained`` (nothing left to decode)
- ``prefill_chunks_total`` / ``prefill_chunk_steps_total`` counters —
  chunked-prefill programs dispatched, and scheduling steps that
  dispatched any: their ratio is chunks per step (a step may spend up to
  ``batch_size``); ``prefill_budget_exhausted_total`` counts the steps
  whose budget ran out with a chunk still waiting
- ``loop_passes_total{phase}`` / ``loop_exit_tokens_total{step}``
  counters — a looped decoder's decode windows, counted inside the program
  and added where a window is drained: passes over the layer stack its
  steps ran (over ``decode_steps_total``: passes a step), and decoded
  tokens by the pass the exit gate took their logits from
- ``ssm_slot_layer_steps_total`` / ``ssm_scan_chunks_total`` counters — a
  state-space decoder's work on its recurrent state: decode updates (live
  slots x state-space layers a step, counted inside the window's program
  and added where it is drained: each reads and writes one slot's state of
  one layer), and blocks of tokens through the chunked scan (state-space
  layers x blocks of the padded bucket, a prefill or chunk program)
- ``kv_cache_layers`` / ``kv_bytes_per_token`` gauges — layers of K/V a
  token holds and its bytes over all of them: what a pool is sized from
- ``programs_built_total{kind}`` counter — engine programs built (compiled
  or loaded from a cache) by ``decode`` / ``prefill``; growth after
  warm-up means a live request hit a new shape
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from dstack_tpu.telemetry.recorder import (
    LATENCY_BUCKETS,
    MetricsRecorder,
    RATIO_BUCKETS,
)

from dstack_tpu.serving.wire import LOAD_HEADER_PREFIX

PREFIX = "dstack_serving_"

#: the phases of the engine's loop (``serving/engine.py``): each is an
#: ``engine.<phase>`` profiler span and a label value of the two
#: ``engine_phase*`` counter families
PHASES = ("wait_for_work", "admit", "prefill", "chunk", "first_token",
          "dispatch_window", "pull", "emit", "build_program")
#: why a scheduling step with a decode window in flight dispatched none
#: behind it (``window_chain_breaks_total{reason}``)
CHAIN_BREAKS = ("admission", "prompt_completed", "drained")

#: response-header prefix the serving server uses to piggyback its load
#: snapshot on every proxied response (the gateway's passive load feed —
#: zero extra polling RPS); the name itself lives in serving/wire.py;
#: header suffix -> (snapshot field, parser)
LOAD_HEADER_FIELDS = {
    "Active": ("active_slots", int),
    "Queue": ("queue_depth", int),
    "Kv": ("kv_utilization", float),
    "Backlog": ("prefill_backlog_tokens", int),
    "Capacity": ("capacity_slots", int),
    # 0/1 — a draining replica finishes in-flight streams but admits no
    # new requests; routers must skip it (gateway drain-and-migrate)
    "Draining": ("draining", int),
    # 0/1 — DISTINCT from draining: a still-compiling (or unactivated
    # standby) replica has never served; routers and admission must not
    # count it toward routable capacity, but nothing should tear it
    # down — it is seconds from being capacity (elastic/standby.py)
    "Warming": ("warming", int),
}


def load_headers(snapshot: Dict) -> Dict[str, str]:
    """Render a load snapshot as ``X-Dstack-Load-*`` response headers.
    Integers render via str() — ``format(v, "g")`` would flip 7+ digit
    counts (a deep prefill backlog) into rounded scientific notation."""
    out = {}
    for suffix, (field, _parse) in LOAD_HEADER_FIELDS.items():
        if field in snapshot:
            v = snapshot[field]
            out[LOAD_HEADER_PREFIX + suffix] = (
                str(v) if isinstance(v, int) else format(v, "g"))
    return out


def parse_load_headers(headers) -> Optional[Dict]:
    """Inverse of :func:`load_headers`: pull the load snapshot off a
    response's headers.  Returns None when no load headers are present
    (non-dstack upstreams); individual malformed values are skipped
    rather than poisoning the rest."""
    out: Dict = {}
    for suffix, (field, parse) in LOAD_HEADER_FIELDS.items():
        raw = headers.get(LOAD_HEADER_PREFIX + suffix)
        if raw is None:
            continue
        try:
            out[field] = parse(float(raw))
        except (TypeError, ValueError):
            continue
    return out or None


class EngineTelemetry:
    """The engine's metrics recorder.

    ``tracer`` (a `dstack_tpu.telemetry.tracing.RequestTracer`) adds
    per-request attribution on top of the aggregates: the engine's
    scheduler stamps (submitted/admitted/first-token/finished, plus the
    KV-stall stamp) become spans at request finish — zero live span
    bookkeeping inside the decode loop — and the latency histograms
    attach the request's trace id as an OpenMetrics exemplar so a p99
    bucket links straight to an example trace.  ``tracer=None`` (the
    default, or ``DSTACK_TPU_TRACING=0``) keeps every added path at one
    ``is None`` check.
    """

    def __init__(self, tracer=None) -> None:
        self.tracer = tracer
        self.recorder = MetricsRecorder()
        r = self.recorder
        self.queue_wait = r.histogram(PREFIX + "queue_wait_seconds")
        self.ttft = r.histogram(PREFIX + "ttft_seconds")
        self.inter_token = r.histogram(PREFIX + "inter_token_seconds")
        self.e2e = r.histogram(PREFIX + "e2e_seconds")
        self.prefill_occupancy = r.histogram(
            PREFIX + "batch_occupancy", RATIO_BUCKETS,
            labels={"phase": "prefill"})
        self.decode_occupancy = r.histogram(
            PREFIX + "batch_occupancy", RATIO_BUCKETS,
            labels={"phase": "decode"})
        self.kv_utilization = r.gauge(PREFIX + "kv_utilization")
        self.kv_utilization_peak = r.gauge(PREFIX + "kv_utilization_peak")
        self.active_slots = r.gauge(PREFIX + "active_slots")
        self.queue_depth = r.gauge(PREFIX + "queue_depth")
        self.prefill_backlog = r.gauge(PREFIX + "prefill_backlog_tokens")
        self.prefill_tokens = r.counter(PREFIX + "prefill_tokens_total")
        self.decode_tokens = r.counter(PREFIX + "decode_tokens_total")
        self.decode_steps = r.counter(PREFIX + "decode_steps_total")
        self.decode_slot_steps = r.counter(
            PREFIX + "decode_slot_steps_total")
        self.prefill_chunks = r.counter(PREFIX + "prefill_chunks_total")
        self.prefill_chunk_steps = r.counter(
            PREFIX + "prefill_chunk_steps_total")
        self.prefill_budget_exhausted = r.counter(
            PREFIX + "prefill_budget_exhausted_total")
        self.windows_ahead = r.counter(
            PREFIX + "windows_dispatched_ahead_total")
        self.slot_update_programs = r.counter(
            PREFIX + "engine_slot_update_programs_total")
        self.slot_updates = r.counter(PREFIX + "engine_slot_updates_total")
        self._chain_breaks = {
            reason: r.counter(PREFIX + "window_chain_breaks_total",
                              labels={"reason": reason})
            for reason in CHAIN_BREAKS}
        self._phases = {
            phase: (r.counter(PREFIX + "engine_phase_seconds_total",
                              labels={"phase": phase}),
                    r.counter(PREFIX + "engine_phases_total",
                              labels={"phase": phase}))
            for phase in PHASES}
        self._started_at = time.time()

    # -- engine-thread recording hooks ----------------------------------

    def record_admitted(self, queue_wait: float,
                        trace_id: Optional[str] = None) -> None:
        self.queue_wait.observe(max(queue_wait, 0.0), exemplar=trace_id)

    def record_first_token(self, ttft: float,
                           trace_id: Optional[str] = None) -> None:
        self.ttft.observe(max(ttft, 0.0), exemplar=trace_id)

    def record_finished(self, req) -> None:
        now = req.finished_at or time.time()
        e2e = max(now - req.submitted_at, 0.0)
        outcome = req.finish_reason or "unknown"
        trace_id = getattr(req, "trace_id", None)
        self.e2e.observe(e2e, exemplar=trace_id)
        self.recorder.counter(PREFIX + "requests_total",
                              labels={"outcome": outcome}).inc()
        if self.tracer is not None and trace_id is not None:
            self._record_request_spans(req, trace_id, now, outcome)

    def _record_request_spans(self, req, trace_id: str, now: float,
                              outcome: str) -> None:
        """Engine-side span taxonomy, derived retroactively from the
        request's scheduler stamps (see the class docstring):

        - ``engine.request``     submitted -> finished (replica root)
        - ``engine.queue_wait``  submitted -> slot admission
        - ``engine.kv_wait``     KV-block stall -> admission (paged pool
                                 exhaustion — the starvation signal)
        - ``engine.prefill``     admission -> first token
        - ``engine.decode``      first token -> finished
        """
        t = self.tracer
        status = "error" if outcome == "error" else "ok"
        root = t.record_span(
            "engine.request", trace_id,
            start=req.submitted_at, end=now,
            parent_id=getattr(req, "parent_span_id", None),
            status=status,
            attrs={"finish_reason": outcome, "tokens_out": len(req.output)})
        rid = root["span_id"]
        admitted = getattr(req, "admitted_at", None)
        t.record_span("engine.queue_wait", trace_id,
                      start=req.submitted_at,
                      end=admitted if admitted is not None else now,
                      parent_id=rid)
        stalled = getattr(req, "_kv_stalled_at", None)
        if stalled is not None:
            t.record_span("engine.kv_wait", trace_id, start=stalled,
                          end=admitted if admitted is not None else now,
                          parent_id=rid,
                          attrs={"reason": "kv_blocks_exhausted"})
        first = getattr(req, "first_token_at", None)
        if admitted is not None and first is not None:
            t.record_span("engine.prefill", trace_id, start=admitted,
                          end=first, parent_id=rid,
                          attrs={"prompt_tokens":
                                 len(getattr(req, "tokens", None) or ())})
        if first is not None:
            t.record_span("engine.decode", trace_id, start=first, end=now,
                          parent_id=rid,
                          attrs={"tokens_out": len(req.output),
                                 "finish_reason": outcome})

    def record_prefill(self, n_tokens: int, bucket: int) -> None:
        self.prefill_tokens.inc(n_tokens)
        if bucket > 0:
            self.prefill_occupancy.observe(min(n_tokens / bucket, 1.0))

    def record_prefill_chunks(self, chunks: int, first_of_step: bool,
                              budget_exhausted: bool) -> None:
        """``chunks`` chunked-prefill programs dispatched by one call of the
        scheduler's chunk queue; a scheduling step makes up to two calls
        and is counted with its first chunks.  ``budget_exhausted``: the
        step's budget ran out with a chunk still waiting."""
        self.prefill_chunks.inc(chunks)
        if first_of_step:
            self.prefill_chunk_steps.inc()
        if budget_exhausted:
            self.prefill_budget_exhausted.inc()

    def record_window(self, decoding: int, batch_size: int) -> None:
        """One decode window at dispatch."""
        self.active_slots.set(decoding)
        if batch_size > 0:
            self.decode_occupancy.observe(min(decoding / batch_size, 1.0))

    def record_window_chain(self, broke: Optional[str]) -> None:
        """One scheduling step that began with a decode window in flight:
        it enqueued the next window ahead of that one's drain (``broke``
        None) or broke the chain for the reason given (``CHAIN_BREAKS``)."""
        if broke is None:
            self.windows_ahead.inc()
        else:
            self._chain_breaks[broke].inc()

    def record_slot_update(self, slots: int) -> None:
        """One program wrote the device state of ``slots`` slots."""
        self.slot_update_programs.inc()
        self.slot_updates.inc(slots)

    def record_phase(self, phase: str, self_seconds: float) -> None:
        """One ``engine.<phase>`` span of the engine's loop closed, with
        the time it spent outside the phases opened inside it."""
        seconds, count = self._phases[phase]
        seconds.inc(self_seconds)
        count.inc()

    def record_drain(self, tokens_emitted: int, wall: float,
                     decoding: int = 1, steps: int = 0,
                     batch_size: int = 0) -> None:
        """``wall`` is the dispatch->drain time of one decode window that
        emitted ``tokens_emitted`` tokens across ``decoding`` slots.  The
        PER-REQUEST token gap is wall / (tokens per request) — dividing by
        the total emitted would shrink the metric with batch occupancy
        and understate what any single stream experiences.

        The window's ``steps`` and ``steps * batch_size`` slot-steps are
        counted HERE, with
        the tokens they produced, so that tokens over slot-steps between
        any two readings compares the same windows."""
        self.decode_steps.inc(steps)
        self.decode_slot_steps.inc(steps * batch_size)
        if tokens_emitted <= 0:
            return
        self.decode_tokens.inc(tokens_emitted)
        self.inter_token.observe(
            max(wall, 0.0) * max(decoding, 1) / tokens_emitted)

    def record_kv_utilization(self, fraction: float) -> None:
        fraction = min(max(fraction, 0.0), 1.0)
        self.kv_utilization.set(fraction)
        if fraction > self.kv_utilization_peak.value:
            self.kv_utilization_peak.set(fraction)

    def record_queue_depth(self, depth: int) -> None:
        self.queue_depth.set(depth)

    def record_prefill_backlog(self, tokens: int) -> None:
        """Prompt tokens still awaiting a chunked-prefill dispatch across
        all mid-chunking slots (0 when chunking is off or drained)."""
        self.prefill_backlog.set(max(tokens, 0))

    def record_preemption(self, reason: str) -> None:
        self.recorder.counter(PREFIX + "preemptions_total",
                              labels={"reason": reason}).inc()

    def record_program_built(self, kind: str) -> None:
        """The engine built a ``decode`` or ``prefill`` program: first use
        of a shape, a compile or a cache load."""
        self.recorder.counter(PREFIX + "programs_built_total",
                              labels={"kind": kind}).inc()

    def record_expert_load(self, held: float, absent: float,
                           load_max: float, load_mean: float,
                           touched: float, rows_computed: float) -> None:
        """One drained decode window of a model with routed experts, from
        the sums its program returned: token-expert pairs that fell on
        experts ``held`` on this chip and on ``absent`` ones, and, summed
        over the window's expert layer-steps, the largest and the mean
        number of pairs one held expert got and the number of held experts
        that got any (``touched``: their weights are what the step read)
        and the rows the grouped product computed for them
        (``rows_computed``: row tiles visited x the tile's rows; ``held``
        over it is the share of the product that is not padding)."""
        r = self.recorder
        r.counter(PREFIX + "moe_pairs_total",
                  labels={"where": "held"}).inc(held)
        r.counter(PREFIX + "moe_pairs_total",
                  labels={"where": "absent"}).inc(absent)
        r.counter(PREFIX + "moe_expert_load_max_sum").inc(load_max)
        r.counter(PREFIX + "moe_expert_load_mean_sum").inc(load_mean)
        r.counter(PREFIX + "moe_experts_touched_sum").inc(touched)
        r.counter(PREFIX + "moe_rows_computed_total").inc(rows_computed)

    def record_loop_passes(self, passes: float, exit_tokens) -> None:
        """One drained decode window of a looped decoder, from the sums its
        program returned: the layer-stack ``passes`` its steps ran, and
        its decoded tokens by the pass their logits were taken from."""
        r = self.recorder
        r.counter(PREFIX + "loop_passes_total",
                  labels={"phase": "decode"}).inc(passes)
        for step, tokens in enumerate(exit_tokens):
            r.counter(PREFIX + "loop_exit_tokens_total",
                      labels={"step": str(step)}).inc(tokens)

    def record_ssm_steps(self, slot_layer_steps: float) -> None:
        """One drained decode window of a state-space decoder: the updates
        of one live slot's state in one layer its steps ran."""
        self.recorder.counter(
            PREFIX + "ssm_slot_layer_steps_total").inc(slot_layer_steps)

    def record_ssm_scan_chunks(self, chunks: int) -> None:
        """One prefill or chunk program of a state-space decoder: the
        blocks of tokens its layers' chunked scans ran."""
        self.recorder.counter(PREFIX + "ssm_scan_chunks_total").inc(chunks)

    def record_kv_geometry(self, cache_layers: int,
                           bytes_per_token: int) -> None:
        """What a KV pool is sized from: the layers of cache a token holds
        (a looped decoder: passes x layers) and its bytes over all of
        them."""
        self.recorder.gauge(PREFIX + "kv_cache_layers").set(cache_layers)
        self.recorder.gauge(PREFIX + "kv_bytes_per_token").set(
            bytes_per_token)

    def record_recurrent_state_bytes(self, nbytes: int) -> None:
        """Bytes of per-slot recurrent state (linear-attention states,
        convolution tails, state-space states) the engine holds beside the
        paged pool."""
        self.recorder.gauge(PREFIX + "recurrent_state_bytes").set(nbytes)

    # -- read side -------------------------------------------------------

    def load_snapshot(self) -> Dict:
        """O(1) load view for ``/load`` and the ``X-Dstack-Load-*``
        headers: four gauge reads, no iteration, no locks.  The gauges are
        refreshed by the engine at submit/dispatch cadence, which is
        exactly the freshness a router can use."""
        return {
            "active_slots": int(self.active_slots.value),
            "queue_depth": int(self.queue_depth.value),
            "kv_utilization": round(self.kv_utilization.value, 4),
            "prefill_backlog_tokens": int(self.prefill_backlog.value),
        }

    def prometheus_samples(self) -> List:
        return self.recorder.samples()

    def stats(self) -> Dict:
        """JSON for ``/stats``: the recorder's summary plus uptime.

        The histogram snapshots inside are the gateway's aggregation
        input (mergeable across replicas); ``percentiles`` are this
        replica's own p50/p95/p99.
        """
        out = self.recorder.summary()
        out["uptime_seconds"] = max(time.time() - self._started_at, 0.0)
        return out


def make_engine_telemetry(env: Optional[dict] = None,
                          ) -> Optional[EngineTelemetry]:
    """Env-gated constructor: ``DSTACK_TPU_SERVING_TELEMETRY=0`` disables
    (the engine then carries ``telemetry=None`` and the hot path pays a
    single ``is None`` check).  Request tracing rides the same instance
    and is separately gated by ``DSTACK_TPU_TRACING`` (tracing.py)."""
    import os

    env = env if env is not None else os.environ
    if str(env.get("DSTACK_TPU_SERVING_TELEMETRY", "1")).lower() in (
            "0", "false", "off", "no"):
        return None
    from dstack_tpu.telemetry.tracing import make_tracer

    return EngineTelemetry(tracer=make_tracer(env))


__all__ = ["EngineTelemetry", "make_engine_telemetry", "PREFIX", "PHASES",
           "CHAIN_BREAKS",
           "LATENCY_BUCKETS", "RATIO_BUCKETS",
           "LOAD_HEADER_PREFIX", "LOAD_HEADER_FIELDS",
           "load_headers", "parse_load_headers"]
