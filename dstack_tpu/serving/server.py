"""OpenAI-compatible HTTP server over the continuous-batching engine.

The JAX model server that `service` runs launch behind the control plane's
proxy/gateway (the reference fronts SGLang/vLLM; this is the TPU-native
equivalent). Endpoints: /health, /v1/models, /v1/completions,
/v1/chat/completions (non-streaming and SSE streaming).

Run: python -m dstack_tpu.serving.server --config tiny --port 8000
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import threading
import time
import uuid
from typing import Optional

from aiohttp import web

from dstack_tpu.models.lfm2 import Lfm2MoeConfig
from dstack_tpu.models.nemotron_h import NemotronHConfig
from dstack_tpu.models.ling_hybrid import LingHybridConfig
from dstack_tpu.models.ouro import OuroConfig
from dstack_tpu.models.llama import LlamaConfig
from dstack_tpu.serving import deadlines
from dstack_tpu.serving.engine import EngineDraining, InferenceEngine, Request
from dstack_tpu.serving.tokenizer import load_tokenizer
from dstack_tpu.serving.wire import PD_PHASE_HEADER
from dstack_tpu.telemetry import tracing
from dstack_tpu.telemetry.serving import load_headers
from dstack_tpu.utils.jax_runtime import (
    device_memory,
    device_report,
    enable_persistent_cache,
)

logger = logging.getLogger(__name__)


def _arr_to_wire(arr) -> dict:
    import base64

    return {
        "b64": base64.b64encode(arr.tobytes()).decode(),
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
    }


def _arr_from_wire(obj):
    import base64

    import ml_dtypes  # ships with jax
    import numpy as np

    dtype = (ml_dtypes.bfloat16 if obj["dtype"] == "bfloat16"
             else np.dtype(obj["dtype"]))
    return np.frombuffer(
        base64.b64decode(obj["b64"]), dtype=dtype
    ).reshape(obj["shape"]).copy()

CONFIGS = {
    "tiny": LlamaConfig.tiny,
    "llama3-1b": LlamaConfig.llama3_1b,
    "llama3-8b": LlamaConfig.llama3_8b,
    "llama3-70b": LlamaConfig.llama3_70b,
    # the hybrid family (KDA + MLA + routed experts); needs --paged
    "ling-hybrid-tiny": LingHybridConfig.tiny,
    # a looped decoder (the layer stack run ut_steps times, a K/V cache for
    # every pass), served by the Llama family's programs
    "ouro-tiny": OuroConfig.tiny,
    "ouro-2.6b": OuroConfig.ouro_2_6b,
    # the LFM2-MoE family (short convolutions + GQA + routed experts);
    # needs --paged.  The 9-layer cut is the benchmark's pipeline stage
    "lfm2-tiny": Lfm2MoeConfig.tiny,
    "lfm2-24b-a2b-9l": Lfm2MoeConfig.lfm2_24b_a2b_9l,
    # the Nemotron-H family (Mamba-2 + routed relu^2 experts + GQA without
    # positions); needs --paged.  The 9-block cut is the benchmark's chip
    # of EP 2
    "nemotron-h-tiny": NemotronHConfig.tiny,
    "nemotron-3-nano-30b-a3b-9l-ep2":
        NemotronHConfig.nemotron_3_nano_30b_a3b_9l_ep2,
}


class ServingApp:
    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer,
        model_name: str = "dstack-tpu-model",
        snapshot_dir: Optional[str] = None,
        standby: bool = False,
        seed_rate_bps: float = 0.0,
    ) -> None:
        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        #: published snapshot dir this replica can SEED to joining peers
        #: (GET /elastic/weights/*) — None disables the seeding routes
        self.snapshot_dir = snapshot_dir
        #: seeder-side transfer pacing (bytes/s; 0 = unlimited) so weight
        #: streaming stays below serving traffic
        self.seed_rate_bps = float(seed_rate_bps)
        #: standby replica: compiled + warmed but refusing /v1 until the
        #: gateway activates it (POST /elastic/standby/activate)
        self.standby = standby
        #: still compiling/warming — reported on /load as ``warming`` so
        #: routers and admission never count this replica as capacity
        self.warming = False
        #: set when the warmup raised: the replica never starts serving and
        #: /health answers 503 with this text (it also stays ``warming``,
        #: so /v1 keeps refusing and no router counts it as capacity)
        self.warmup_error: Optional[str] = None
        self._activated_at: Optional[float] = None
        #: request tracer (telemetry/tracing.py) — rides the engine's
        #: telemetry so the scheduler spans and the HTTP spans share one
        #: ring; None when telemetry or DSTACK_TPU_TRACING is off
        self.tracer = getattr(
            getattr(engine, "telemetry", None), "tracer", None)
        self._thread = threading.Thread(
            target=engine.run_forever, daemon=True, name="engine"
        )

    def start_engine(self, warm: bool = False) -> None:
        """Start the engine loop; ``warm=True`` first drives one warmup
        request on a background thread (compiling every needed program,
        or pulling it from the compile cache) with ``warming`` visible on
        ``/load`` the whole time, then starts the loop.  The warmup runs
        BEFORE the engine thread so the two never race ``step()``.  A
        warmup that raises (a program the compiler refuses, no memory) is
        what every request would hit next: the loop is never started and
        ``/health`` reports the error instead of ``ok``."""
        if not warm:
            self._thread.start()
            return
        self.warming = True

        def _warm() -> None:
            try:
                self.engine.warmup()
            except Exception as e:  # noqa: BLE001 — reported on /health
                logger.exception("warmup failed; this replica will not serve")
                self.warmup_error = f"{type(e).__name__}: {e}"
                return
            self.warming = False
            self._thread.start()

        threading.Thread(target=_warm, daemon=True,
                         name="engine-warm").start()

    def activate_standby(self) -> dict:
        """Flip a standby replica live: the entire scale-up critical
        path once warming is done — no provision, no weights, no
        compile.  Idempotent; returns the activation report."""
        was_standby = self.standby
        self.standby = False
        if was_standby and self._activated_at is None:
            self._activated_at = time.time()
        return {
            "activated": was_standby,
            "warming": bool(self.warming),
            "standby": False,
        }

    # -- request plumbing -------------------------------------------------

    def _make_request(self, prompt_ids, payload) -> Request:
        return Request(
            tokens=prompt_ids,
            max_new_tokens=int(payload.get("max_tokens", 128)),
            temperature=float(payload.get("temperature") or 0.0),
            top_p=float(payload.get("top_p") or 1.0),
            top_k=int(payload.get("top_k") or 0),
            eos_id=self.tokenizer.eos_id,
        )

    def _install_stop(self, req: Request, payload) -> dict:
        """OpenAI ``stop`` sequences: watch the decoded text as tokens
        arrive, cancel the request at the first match, and remember the
        clip offset so responses exclude the stop string.  Wraps (chains)
        any on_token already installed.  Returns the watcher state
        ({"clip": char_index or None})."""
        stops = payload.get("stop")
        if isinstance(stops, str):
            stops = [stops]
        # non-string entries must not reach the engine thread (a TypeError
        # there would crash-fail every in-flight request)
        stops = [s for s in (stops or []) if isinstance(s, str) and s][:4]
        state: dict = {"clip": None, "stops": stops}
        req._stop_state = state
        if not stops:
            return state
        prev = req.on_token
        # this runs per token ON THE ENGINE THREAD: scan only a bounded
        # decoded tail (stop-length + slack tokens — enough for any match
        # whose final character just arrived), and pay the one full decode
        # only when a match is seen, to compute the global clip offset
        # bound the window by ENCODED length: with byte-level BPE a
        # multi-byte stop string (CJK/emoji) can span up to one token per
        # UTF-8 byte, so a character count would let long stops scroll out
        # of the tail and be missed forever
        tail_tokens = max(len(s.encode("utf-8")) for s in stops) + 8

        def watch(token: int) -> None:
            if prev is not None:
                prev(token)
            if state["clip"] is not None:
                return
            tail = self.tokenizer.decode(req.output[-tail_tokens:])
            if not any(s in tail for s in stops):
                return
            text = self.tokenizer.decode(req.output)
            hits = [i for i in (text.find(s) for s in stops) if i >= 0]
            if hits:
                state["clip"] = min(hits)
                req.cancel(reason="stop")

        req.on_token = watch
        return state

    @staticmethod
    def _clip_text(req: Request, text: str) -> str:
        clip = getattr(req, "_stop_state", {}).get("clip")
        return text if clip is None else text[:clip]

    async def _await_done(self, req: Request) -> None:
        loop = asyncio.get_running_loop()

        def wait() -> None:
            # bounded waits so a cancelled-while-queued request releases
            # this executor thread promptly (the engine only finalizes
            # queued cancellations when the request reaches admission)
            while not req.done.wait(timeout=0.5):
                if req.cancelled:
                    return

        await loop.run_in_executor(None, wait)

    # -- load snapshot (gateway routing input) -----------------------------

    def load_snapshot(self) -> Optional[dict]:
        """O(1) load view for ``/load`` and the ``X-Dstack-Load-*``
        response headers: the telemetry gauges plus slot capacity.  None
        when telemetry is disabled (the DSTACK_TPU_SERVING_TELEMETRY
        gate) — the endpoint then 404s and no headers are attached."""
        tel = getattr(self.engine, "telemetry", None)
        if tel is None or not hasattr(tel, "load_snapshot"):
            return None
        snap = tel.load_snapshot()
        cap = int(getattr(self.engine, "batch_size", 0) or 0)
        snap["capacity_slots"] = cap
        busy = snap["active_slots"] + snap["queue_depth"]
        # > 1.0 means requests are queueing behind full slots — exactly
        # the signal a router spills away from
        snap["load"] = round(busy / cap, 4) if cap else float(busy)
        # drain mode rides the same passive feed: routers that see
        # draining=1 stop sending new work without any extra polling
        snap["draining"] = int(bool(getattr(self.engine, "draining", False)))
        # warming is DISTINCT from draining: a still-compiling (or
        # not-yet-activated standby) replica has never served and must
        # not count toward routable capacity — but it is healthy and
        # about to be, so orchestrators must not tear it down either
        snap["warming"] = int(bool(self.warming or self.standby))
        cache = getattr(self.engine, "compile_cache", None)
        if cache is not None:
            snap.update(cache.snapshot())
        return snap

    @staticmethod
    def _draining_response() -> web.Response:
        return web.json_response(
            {"detail": "replica draining, retry elsewhere"},
            status=503, headers={"Retry-After": "1"},
        )

    def _refuse_if_draining(self) -> Optional[web.Response]:
        """503 + Retry-After for NEW generation requests on a draining
        replica — in-flight streams keep running to completion; the
        gateway's migrate flow has already routed new traffic to the
        successor, so this only fires for stragglers/direct callers."""
        if getattr(self.engine, "draining", False):
            return self._draining_response()
        return None

    @staticmethod
    def _warming_response() -> web.Response:
        return web.json_response(
            {"detail": "replica warming, not yet serving"},
            status=503, headers={"Retry-After": "2"},
        )

    def _refuse_if_warming(self) -> Optional[web.Response]:
        """503 for generation requests while the replica is still
        compiling/warming or is an unactivated standby — the engine loop
        is not running yet, so accepting would hang the request; the
        gateway never routes here anyway (warming rides the load
        headers, standby rides the registry)."""
        if self.warming or self.standby:
            return self._warming_response()
        return None

    def _submit_or_refuse(self, req: Request) -> Optional[web.Response]:
        """Close the check-then-submit race: a drain that begins after
        `_refuse_if_draining` passed (handlers await the body/tokenize in
        between) must still yield the documented 503, not an unhandled
        `EngineDraining` 500."""
        try:
            self.engine.submit(req)
        except EngineDraining:
            return self._draining_response()
        return None

    # -- deadlines (grey-failure defense) ----------------------------------

    @staticmethod
    def _deadline_response() -> web.Response:
        return web.json_response(
            {"detail": "deadline exceeded"}, status=504
        )

    def _install_deadline(self, req: Optional[Request],
                          request: web.Request) -> Optional[web.Response]:
        """Honor an inbound ``X-Dstack-Deadline`` budget: already-expired
        requests are refused 504 up front (no tokenize/prefill burned);
        otherwise the engine request carries the absolute deadline so
        queue eviction and mid-decode cancellation work engine-side."""
        remaining = deadlines.parse_remaining(request.headers)
        if remaining is None:
            return None
        if remaining <= 0.0:
            return self._deadline_response()
        if req is not None:
            req.deadline = time.time() + remaining
        return None

    @staticmethod
    def _finished_past_deadline(req: Request) -> bool:
        return req.finish_reason == "deadline"

    def _wedged_response(self) -> Optional[web.Response]:
        """503 when the engine watchdog sees a stuck scheduling step —
        the replica's /load health fails, so routers stop sending work
        and orchestrators can replace it, instead of every caller
        hanging to its deadline on a wedged device runtime."""
        if getattr(self.engine, "wedged", False):
            return web.json_response(
                {"detail": "engine wedged: decode step stuck past the "
                           "watchdog window"},
                status=503, headers={"Retry-After": "5"},
            )
        return None

    @web.middleware
    async def load_header_middleware(self, request: web.Request, handler):
        """Piggyback the load snapshot on every response so the gateway
        learns replica load passively, with zero extra polling RPS.
        Streaming responses prepare inside their handlers and attach the
        headers there (headers cannot change after prepare())."""
        resp = await handler(request)
        if isinstance(resp, web.StreamResponse) and not resp.prepared:
            snap = self.load_snapshot()
            if snap is not None:
                resp.headers.update(load_headers(snap))
        return resp

    @web.middleware
    async def tracing_middleware(self, request: web.Request, handler):
        """Per-request ``replica.request`` span around the OpenAI
        endpoints: continues an inbound W3C ``traceparent`` (or mints a
        fresh trace), hands the context to the handler via
        ``request["trace"]`` so the engine `Request` inherits it, stamps
        the trace id on the response as ``X-Dstack-Trace-Id`` (an
        internal header every proxy leg strips from client responses),
        and runs the tail sampler once the request — including a full
        SSE stream — has completed."""
        tracer = self.tracer
        if tracer is None or not request.path.startswith("/v1/"):
            return await handler(request)
        ctx = tracing.parse_traceparent(
            request.headers.get(tracing.TRACEPARENT_HEADER))
        trace_id, parent = ctx if ctx is not None else (
            tracing.new_trace_id(), None)
        span = tracer.start_span(
            "replica.request", trace_id=trace_id, parent_id=parent,
            attrs={"path": request.path})
        request["trace"] = (trace_id, span.span_id)
        status = 500
        try:
            resp = await handler(request)
            status = resp.status
            if isinstance(resp, web.StreamResponse) and not resp.prepared:
                resp.headers[tracing.TRACE_ID_HEADER] = trace_id
            return resp
        finally:
            if status >= 500:
                span.status = "error"
            span.set_attr("status", status)
            span.end()
            tracer.finish_trace(trace_id, span.duration,
                                error=span.status == "error")

    # -- handlers ----------------------------------------------------------

    async def load(self, request: web.Request) -> web.Response:
        wedged = self._wedged_response()
        if wedged is not None:
            return wedged
        snap = self.load_snapshot()
        if snap is None:
            return web.json_response(
                {"detail": "telemetry disabled"}, status=404
            )
        return web.json_response(snap)

    async def drain(self, request: web.Request) -> web.Response:
        """Enter drain mode (idempotent): stop admitting, finish in-flight
        streams.  Response reports whether the engine is already fully
        drained so orchestrators can poll this same endpoint.

        Body ``{"drain": false}`` reverses it (aborted migration,
        maintenance over) — note an in-flight gateway migration's poll
        loop re-drains on its next poll, so undrain only sticks for
        standalone drains."""
        want = True
        try:
            body = await request.json()
        except Exception:
            body = None
        if isinstance(body, dict) and body.get("drain") is False:
            want = False
        if want:
            self.engine.begin_drain()
        else:
            self.engine.end_drain()
        return web.json_response({
            "status": "draining" if self.engine.draining else "accepting",
            "drained": bool(self.engine.drained),
        })

    # -- elastic: compile-cache + weight seeding, standby ------------------

    async def elastic_compile(self, request: web.Request) -> web.Response:
        """Serve one serialized executable from the local compile cache
        — the peer-fetch path a scaling-up replica hits on a local miss
        (elastic/compile_cache.py)."""
        cache = getattr(self.engine, "compile_cache", None)
        if cache is None:
            return web.json_response(
                {"detail": "compile cache disabled"}, status=404)
        key = request.match_info["key"]
        if not (key and all(c in "0123456789abcdef" for c in key)):
            return web.json_response({"detail": "bad cache key"}, status=400)
        data = cache.get_bytes(key)
        if data is None:
            return web.json_response(
                {"detail": f"no cached executable {key[:12]}…"}, status=404)
        return web.Response(body=data,
                            content_type="application/octet-stream")

    def _seed_step_dir(self):
        """Latest published snapshot step dir to seed from, or None."""
        if not self.snapshot_dir:
            return None
        from pathlib import Path

        from dstack_tpu.models.checkpoint import latest_snapshot_step

        step = latest_snapshot_step(self.snapshot_dir)
        if step is None:
            return None
        return Path(self.snapshot_dir) / f"step_{step:08d}"

    async def elastic_weights_manifest(self, request: web.Request
                                       ) -> web.Response:
        step_dir = self._seed_step_dir()
        if step_dir is None:
            return web.json_response(
                {"detail": "no published snapshot to seed"}, status=404)
        return web.Response(body=(step_dir / "manifest.json").read_bytes(),
                            content_type="application/json")

    async def elastic_weights_shard(self, request: web.Request
                                    ) -> web.StreamResponse:
        """Stream one host shard file, chunked and paced below serving
        traffic (``seed_rate_bps``; 0 = unlimited).  Only names the
        manifest format can produce are served — no path traversal."""
        import re

        step_dir = self._seed_step_dir()
        if step_dir is None:
            return web.json_response(
                {"detail": "no published snapshot to seed"}, status=404)
        name = request.match_info["name"]
        if not re.fullmatch(r"host_\d{5}\.npz", name):
            return web.json_response(
                {"detail": "not a shard file name"}, status=400)
        path = step_dir / name
        if not path.exists():
            return web.json_response(
                {"detail": f"no shard {name}"}, status=404)
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "application/octet-stream",
                     "Content-Length": str(path.stat().st_size)})
        await resp.prepare(request)
        chunk_bytes = 1 << 20
        with open(path, "rb") as f:
            while True:
                block = f.read(chunk_bytes)
                if not block:
                    break
                await resp.write(block)
                if self.seed_rate_bps > 0:
                    # seeding must lose to serving: pace the transfer and
                    # yield the event loop between chunks
                    await asyncio.sleep(len(block) / self.seed_rate_bps)
        await resp.write_eof()
        return resp

    async def elastic_standby_status(self, request: web.Request
                                     ) -> web.Response:
        return web.json_response({
            "standby": bool(self.standby),
            "warming": bool(self.warming),
            "activated_at": self._activated_at,
        })

    async def elastic_standby_activate(self, request: web.Request
                                       ) -> web.Response:
        """Gateway scale-up path: flip this pre-warmed standby live.
        409 while still warming — the caller should pick another standby
        or fall back to a cold provision rather than wait here."""
        if self.warming:
            return web.json_response(
                {"detail": "standby still warming", "warming": True},
                status=409, headers={"Retry-After": "2"})
        return web.json_response(self.activate_standby())

    async def health(self, request: web.Request) -> web.Response:
        wedged = self._wedged_response()
        if wedged is not None:
            return wedged
        status = ("error" if self.warmup_error is not None
                  else "warming" if (self.warming or self.standby)
                  else "draining"
                  if getattr(self.engine, "draining", False)
                  else "ok")
        out = {"status": status, "model": self.model_name,
               "device": device_report()}
        if self.warmup_error is not None:
            out["error"] = self.warmup_error
            return web.json_response(out, status=503)
        return web.json_response(out)

    async def metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition of the engine's telemetry.

        Rendered with the same server/telemetry/exposition renderer the
        control plane uses, so the PR-1 per-job scraper (pointed here by
        the auto-declared ``metrics:`` block on service runs) republishes
        these series with project/run/job/replica labels verbatim.

        Scrapers that negotiate OpenMetrics (``Accept:
        application/openmetrics-text``) additionally get *exemplars* on
        the latency histogram buckets — trace ids linking a p99 bucket to
        an example request trace.  The classic text format has no
        exemplar syntax, so the default page stays exemplar-free and any
        classic Prometheus scraper still parses it."""
        from dstack_tpu.server.telemetry.exposition import render

        openmetrics = "application/openmetrics-text" in (
            request.headers.get("Accept") or "")
        tel = getattr(self.engine, "telemetry", None)
        lines = [] if tel is None else render(tel.prometheus_samples(),
                                              openmetrics=openmetrics)
        if openmetrics:
            lines.append("# EOF")
            return web.Response(
                text="\n".join(lines) + "\n",
                content_type="application/openmetrics-text",
                charset="utf-8")
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain", charset="utf-8")

    # -- request traces (telemetry/tracing.py) ----------------------------

    async def traces(self, request: web.Request) -> web.Response:
        """Recent + tail-retained traces on this replica (newest first).
        404 when tracing is off — same contract as ``/load``."""
        if self.tracer is None:
            return web.json_response(
                {"detail": "tracing disabled"}, status=404
            )
        return web.json_response(self.tracer.summary())

    async def trace_detail(self, request: web.Request) -> web.Response:
        if self.tracer is None:
            return web.json_response(
                {"detail": "tracing disabled"}, status=404
            )
        trace_id = request.match_info["trace_id"]
        spans = self.tracer.trace(trace_id)
        if not spans:
            return web.json_response(
                {"detail": f"unknown trace {trace_id}"}, status=404
            )
        return web.json_response({"trace_id": trace_id, "spans": spans})

    async def stats(self, request: web.Request) -> web.Response:
        """JSON latency/throughput summary: per-histogram p50/p95/p99 plus
        the mergeable bucket snapshots the gateway aggregates across
        replicas into per-service percentiles."""
        tel = getattr(self.engine, "telemetry", None)
        out = {"model": self.model_name}
        if tel is not None:
            out.update(tel.stats())
        cache = getattr(self.engine, "compile_cache", None)
        if cache is not None:
            out["compile_cache"] = cache.snapshot()
        out["warming"] = bool(self.warming)
        out["standby"] = bool(self.standby)
        out["device_memory"] = device_memory()
        if getattr(self.engine, "prefix_cache", False):
            # the allocator's own counters: lookups, hit_blocks, evictions
            out["prefix_cache"] = dict(self.engine._alloc.stats)
        return web.json_response(out)

    async def models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {
                        "id": self.model_name,
                        "object": "model",
                        "created": int(time.time()),
                        "owned_by": "dstack-tpu",
                    }
                ],
            }
        )

    async def completions(self, request: web.Request) -> web.StreamResponse:
        refused = self._refuse_if_draining() or self._refuse_if_warming()
        if refused is not None:
            return refused
        payload = await request.json()
        prompt = payload.get("prompt", "")
        if isinstance(prompt, list):
            prompt = "".join(prompt)
        ids = self.tokenizer.encode(prompt)
        marker, req = self._phase_request(ids, payload, request)
        expired = self._install_deadline(req, request)
        if expired is not None:
            return expired
        if marker == "prefill":
            return await self._prefill_phase(ids, payload)
        if payload.get("stream"):
            return await self._stream(request, req, chat=False, payload=payload)
        self._install_stop(req, payload)
        refused = self._submit_or_refuse(req)
        if refused is not None:
            return refused
        try:
            await self._await_done(req)
        except asyncio.CancelledError:
            req.cancel()  # client went away: free the slot
            raise
        if self._finished_past_deadline(req):
            # expired in queue or mid-decode: the 504 is the honest
            # answer — by definition nobody is waiting for the body
            return self._deadline_response()
        text = self._clip_text(req, self.tokenizer.decode(req.output))
        return web.json_response(
            {
                "id": f"cmpl-{uuid.uuid4().hex[:12]}",
                "object": "text_completion",
                "created": int(time.time()),
                "model": payload.get("model", self.model_name),
                "choices": [
                    {
                        "index": 0,
                        "text": text,
                        "finish_reason": req.finish_reason,
                    }
                ],
                "usage": {
                    "prompt_tokens": len(ids),
                    "completion_tokens": len(req.output),
                    "total_tokens": len(ids) + len(req.output),
                },
            }
        )

    # -- PD disaggregation phases -----------------------------------------

    async def _prefill_phase(self, ids, payload) -> web.Response:
        """Phase 1 of a disaggregated completion: compute the prompt KV +
        last-position logits here (the prefill replica) and ship them to
        the router, which forwards them to a decode replica as
        `prefill_result`."""
        import functools

        loop = asyncio.get_running_loop()
        result = await loop.run_in_executor(
            None,
            functools.partial(
                self.engine.prefill_export, ids,
                max_new_tokens=int(payload.get("max_tokens", 128)),
            ),
        )
        return web.json_response({
            "object": "prefill_result",
            "model": payload.get("model", self.model_name),
            "first_token": result["first_token"],
            "length": result["length"],
            "prompt_ids": list(ids),
            "kv_k": _arr_to_wire(result["ks"]),
            "kv_v": _arr_to_wire(result["vs"]),
            "logits": _arr_to_wire(result["logits"]),
        })

    def _request_from_prefill(self, payload) -> Request:
        p = payload["prefill_result"]
        req = self._make_request(list(p["prompt_ids"]), payload)
        req.prefill = {
            "ks": _arr_from_wire(p["kv_k"]),
            "vs": _arr_from_wire(p["kv_v"]),
            "logits": (_arr_from_wire(p["logits"])
                       if p.get("logits") else None),
            "first_token": int(p["first_token"]),
            "length": int(p["length"]),
        }
        return req

    def _phase_request(self, ids, payload, request):
        """Shared PD phase dispatch for both OpenAI endpoints: returns a
        Response (prefill phase) or the Request to run (decode/normal).
        The engine request inherits the tracing middleware's context so
        scheduler spans land in the same trace as the HTTP span."""
        phase = request.headers.get(PD_PHASE_HEADER, "")
        if phase == "prefill":
            return "prefill", None
        if phase == "decode" and payload.get("prefill_result"):
            req = self._request_from_prefill(payload)
        else:
            req = self._make_request(ids, payload)
        trace = request.get("trace")
        if trace is not None:
            req.trace_id, req.parent_span_id = trace
        return None, req

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        refused = self._refuse_if_draining() or self._refuse_if_warming()
        if refused is not None:
            return refused
        payload = await request.json()
        messages = payload.get("messages") or []
        prompt = self.tokenizer.apply_chat_template(messages)
        ids = self.tokenizer.encode(prompt)
        marker, req = self._phase_request(ids, payload, request)
        expired = self._install_deadline(req, request)
        if expired is not None:
            return expired
        if marker == "prefill":
            return await self._prefill_phase(ids, payload)
        if payload.get("stream"):
            return await self._stream(request, req, chat=True, payload=payload)
        self._install_stop(req, payload)
        refused = self._submit_or_refuse(req)
        if refused is not None:
            return refused
        try:
            await self._await_done(req)
        except asyncio.CancelledError:
            req.cancel()  # client went away: free the slot
            raise
        if self._finished_past_deadline(req):
            return self._deadline_response()
        text = self._clip_text(req, self.tokenizer.decode(req.output))
        return web.json_response(
            {
                "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": payload.get("model", self.model_name),
                "choices": [
                    {
                        "index": 0,
                        "message": {"role": "assistant", "content": text},
                        "finish_reason": req.finish_reason,
                    }
                ],
                "usage": {
                    "prompt_tokens": len(ids),
                    "completion_tokens": len(req.output),
                    "total_tokens": len(ids) + len(req.output),
                },
            }
        )

    async def _stream(
        self, request: web.Request, req: Request, chat: bool, payload: dict
    ) -> web.StreamResponse:
        """SSE token streaming (OpenAI chunk format)."""
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            },
        )
        snap = self.load_snapshot()
        if snap is not None:  # prepared here: the middleware can't add them
            resp.headers.update(load_headers(snap))
        trace = request.get("trace")
        if trace is not None:  # ditto for the trace-id feed
            resp.headers[tracing.TRACE_ID_HEADER] = trace[0]
        loop = asyncio.get_running_loop()
        token_q: asyncio.Queue = asyncio.Queue()
        req.on_token = lambda t: loop.call_soon_threadsafe(
            token_q.put_nowait, t
        )
        stop_state = self._install_stop(req, payload)
        # submit BEFORE preparing the SSE response: once prepare() sends
        # the 200 status line, a drain that raced the top-of-handler check
        # could no longer surface as the documented 503
        refused = self._submit_or_refuse(req)
        if refused is not None:
            return refused
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        try:
            await resp.prepare(request)
            return await self._stream_loop(
                resp, req, chat, payload, token_q, stop_state, rid)
        except (asyncio.CancelledError, ConnectionResetError):
            req.cancel()  # client went away mid-stream: free the slot
            raise

    @staticmethod
    def _sse_chunk(rid: str, chat: bool, model: str, *, delta: str = None,
                   finish: str = None) -> dict:
        """One OpenAI streaming chunk (content delta or the final marker)."""
        if finish is None:
            choice = {"index": 0,
                      **({"delta": {"content": delta}} if chat
                         else {"text": delta}),
                      "finish_reason": None}
        else:
            choice = {"index": 0, "delta": {} if chat else None,
                      "text": None if chat else "", "finish_reason": finish}
        return {
            "id": rid,
            "object": "chat.completion.chunk" if chat else "text_completion",
            "created": int(time.time()),
            "model": model,
            "choices": [choice],
        }

    async def _stream_loop(self, resp, req, chat, payload, token_q,
                           stop_state, rid) -> web.StreamResponse:
        sent = 0
        emitted_chars = 0
        pending: list = []
        while True:
            if req.done.is_set() and token_q.empty() and not pending:
                break
            try:
                tok = await asyncio.wait_for(token_q.get(), timeout=0.1)
                pending.append(tok)
            except asyncio.TimeoutError:
                if req.done.is_set() and token_q.empty() and not pending:
                    break
                continue
            # decode accumulated output; emit only complete new text (up to
            # any stop-sequence clip point — decode windows can overshoot a
            # stop match by a burst of tokens).  Tokens are consumed
            # regardless — a token with no printable text (special /
            # partial UTF-8) must not wedge the loop.
            text = self.tokenizer.decode(req.output[: sent + len(pending)])
            clip = stop_state["clip"]
            if clip is not None:
                text = text[:clip]
            elif stop_state["stops"]:
                # hold back any tail that could be the START of a stop
                # sequence — it must not stream out before the match is
                # decided (the post-loop flush emits it if no stop lands)
                hold = 0
                for s in stop_state["stops"]:
                    for k in range(min(len(s), len(text)), 0, -1):
                        if text.endswith(s[:k]):
                            hold = max(hold, k)
                            break
                if hold:
                    text = text[: len(text) - hold]
            delta = text[emitted_chars:]
            emitted_chars = max(emitted_chars, len(text))
            sent += len(pending)
            pending = []
            if not delta:
                continue
            chunk = self._sse_chunk(rid, chat,
                                    payload.get("model", self.model_name),
                                    delta=delta)
            await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
        # flush any text held back for a stop match that never completed
        text = self.tokenizer.decode(req.output)
        if stop_state["clip"] is not None:
            text = text[: stop_state["clip"]]
        tail = text[emitted_chars:]
        if tail:
            chunk = self._sse_chunk(rid, chat,
                                    payload.get("model", self.model_name),
                                    delta=tail)
            await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
        final = self._sse_chunk(rid, chat,
                                payload.get("model", self.model_name),
                                finish=req.finish_reason or "stop")
        await resp.write(f"data: {json.dumps(final)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    def make_app(self) -> web.Application:
        app = web.Application(middlewares=[self.load_header_middleware,
                                           self.tracing_middleware])
        app.router.add_get("/health", self.health)
        app.router.add_get("/metrics", self.metrics)
        app.router.add_get("/stats", self.stats)
        app.router.add_get("/load", self.load)
        app.router.add_post("/drain", self.drain)
        app.router.add_get("/elastic/compile/{key}", self.elastic_compile)
        app.router.add_get("/elastic/weights/manifest",
                           self.elastic_weights_manifest)
        app.router.add_get("/elastic/weights/{name}",
                           self.elastic_weights_shard)
        app.router.add_get("/elastic/standby", self.elastic_standby_status)
        app.router.add_post("/elastic/standby/activate",
                            self.elastic_standby_activate)
        app.router.add_get("/traces", self.traces)
        app.router.add_get("/traces/{trace_id}", self.trace_detail)
        app.router.add_get("/v1/models", self.models)
        app.router.add_post("/v1/completions", self.completions)
        # OpenAI-compatible surface for external clients
        app.router.add_post("/v1/chat/completions", self.chat_completions)  # dtlint: external-surface
        return app


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="tiny", choices=sorted(CONFIGS))
    parser.add_argument("--checkpoint", default=None,
                        help="HF Llama checkpoint dir (*.safetensors) — "
                             "overrides --config with real weights")
    parser.add_argument("--quantize", default=None, choices=["int8"],
                        help="weight-only quantization (serving/quant.py)")
    parser.add_argument("--tokenizer", default=None,
                        help="HF tokenizer name/path (byte fallback if unset)")
    parser.add_argument("--model-name", default=None)
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--max-len", type=int, default=1024)
    parser.add_argument(
        "--tensor-parallel", type=int, default=1, metavar="N",
        help="shard the model over the first N local devices "
             "(Megatron-style TP; for models too big for one chip)")
    parser.add_argument(
        "--paged", action="store_true",
        help="block-paged KV cache (serving/paging.py): requests reserve "
             "only the blocks they need instead of a dense max-len row")
    parser.add_argument("--kv-block-size", type=int, default=32)
    parser.add_argument(
        "--total-kv-blocks", type=int, default=None,
        help="paged-mode pool size; default = batch_size * max_len / block")
    parser.add_argument(
        "--prefix-cache", action="store_true",
        help="reuse KV of shared prompt prefixes across requests "
             "(system prompts, few-shot preambles); implies --paged")
    parser.add_argument(
        "--kv-quantize", choices=["int8", "int4"], default=None,
        help="store the KV cache quantized with per-row scales: int8 "
             "halves attention's HBM reads (the dominant decode cost at "
             "high concurrency) at ~0.6%% RMS row error; int4 packs two "
             "values per byte — a quarter of the bytes, double the "
             "resident slots of int8 — at ~6%% RMS (opt-in accuracy "
             "trade-off, see docs/concepts/services.md)")
    parser.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="N",
        help="prefill long prompts in N-token chunks interleaved with "
             "decode windows (long arrivals stop stalling active streams); "
             f"default: the tuned {InferenceEngine.TUNED_PREFILL_CHUNK} "
             "(overlap sweep winner); 0 disables chunking")
    parser.add_argument(
        "--no-telemetry", action="store_true",
        help="disable the in-process serving telemetry (/metrics + /stats "
             "then serve empty; also DSTACK_TPU_SERVING_TELEMETRY=0)")
    parser.add_argument(
        "--compile-cache", default=None, metavar="DIR",
        help="persistent compile cache root (elastic/compile_cache.py): "
             "serialized executables keyed by HLO+topology, shared with "
             "peers; also DSTACK_COMPILE_CACHE")
    parser.add_argument(
        "--compile-cache-peers", default=None, metavar="URLS",
        help="comma-separated peer base URLs to fetch cache entries from "
             "on local miss; also DSTACK_COMPILE_CACHE_PEERS")
    parser.add_argument(
        "--snapshot-dir", default=None, metavar="DIR",
        help="published snapshot dir (models/checkpoint.py manifest "
             "format) this replica seeds to joining peers over "
             "/elastic/weights/*")
    parser.add_argument(
        "--weight-peers", default=None, metavar="URLS",
        help="comma-separated live-replica base URLs to stream weights "
             "from into --snapshot-dir before start (cold source is the "
             "fallback); also DSTACK_WEIGHT_PEERS")
    parser.add_argument(
        "--seed-rate-bps", type=float, default=0.0, metavar="BPS",
        help="cap seeding transfers at this many bytes/s so weight "
             "streaming stays below serving traffic (0 = unlimited; "
             "also DSTACK_SEED_RATE_BPS)")
    parser.add_argument(
        "--standby", action="store_true",
        help="start as a pre-warmed standby: compile + warm up, then "
             "refuse /v1 (503) until POST /elastic/standby/activate — "
             "the autoscaler's O(seconds) scale-up path")
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)
    jax_cache_dir = enable_persistent_cache()
    logger.info("device %s; jax compilation cache %s",
                json.dumps(device_report()), jax_cache_dir)
    params = None
    model_name = args.model_name or args.config
    if args.checkpoint:
        # real weights: config + params straight from the HF checkpoint
        # (models/checkpoint.py); --tokenizer defaults to the same dir
        from pathlib import Path

        from dstack_tpu.models.checkpoint import load_hf_llama
        from dstack_tpu.serving.tokenizer import ByteTokenizer

        cfg, params = load_hf_llama(args.checkpoint)
        tokenizer = load_tokenizer(args.tokenizer or args.checkpoint)
        if isinstance(tokenizer, ByteTokenizer):
            # real weights + byte fallback = fluent-looking garbage; fail
            # loudly instead
            raise SystemExit(
                f"could not load a tokenizer for {args.checkpoint} "
                "(pass --tokenizer explicitly)"
            )
        model_name = args.model_name or Path(args.checkpoint).name
    else:
        tokenizer = load_tokenizer(args.tokenizer)
        cfg = CONFIGS[args.config]()
    if tokenizer.vocab_size > cfg.vocab_size:
        raise SystemExit(
            f"tokenizer vocab {tokenizer.vocab_size} exceeds model vocab "
            f"{cfg.vocab_size}"
        )
    mesh = None
    if args.tensor_parallel > 1:
        import jax

        from dstack_tpu.parallel.mesh import MeshSpec, build_mesh

        devices = jax.devices()
        if len(devices) < args.tensor_parallel:
            raise SystemExit(
                f"--tensor-parallel {args.tensor_parallel} but only "
                f"{len(devices)} device(s) visible")
        mesh = build_mesh(MeshSpec(tensor=args.tensor_parallel),
                          devices[: args.tensor_parallel])
    from dstack_tpu.telemetry.serving import make_engine_telemetry

    import os as _os

    compile_cache = None
    cache_root = args.compile_cache or _os.environ.get(
        "DSTACK_COMPILE_CACHE", "")
    cache_peers = args.compile_cache_peers or _os.environ.get(
        "DSTACK_COMPILE_CACHE_PEERS", "")
    if cache_root or cache_peers:
        from dstack_tpu.elastic.compile_cache import CompileCache

        compile_cache = CompileCache(
            cache_root or None,
            [p.strip() for p in cache_peers.split(",") if p.strip()])
    weight_peers = [p.strip() for p in
                    (args.weight_peers
                     or _os.environ.get("DSTACK_WEIGHT_PEERS", "")
                     ).split(",") if p.strip()]
    if weight_peers and args.snapshot_dir:
        # pull the published snapshot from a live peer before building
        # the engine — the cold source (GCS / local init) is only the
        # fallback.  Failure is non-fatal: the replica still starts from
        # its cold source, just slower.
        from dstack_tpu.elastic.weight_stream import (
            WeightStreamError,
            pull_weights,
        )

        try:
            report = pull_weights(weight_peers, args.snapshot_dir,
                                  cold_fallback=lambda: -1)
            logger.info("weight pull: %s", report)
            if report["source"] == "peer" and params is None:
                # the streamed snapshot IS this replica's weights: restore
                # it (sha256-verified again on read) instead of serving a
                # fresh random init.  Non-fatal — a snapshot in some other
                # pytree layout (e.g. a full train state) just falls back
                # to the cold init.
                import jax as _jax

                from dstack_tpu.models.checkpoint import read_snapshot
                from dstack_tpu.models.llama import init_params

                try:
                    params, pulled_step = read_snapshot(
                        args.snapshot_dir,
                        init_params(_jax.random.PRNGKey(0), cfg),
                        verify=True)
                    logger.info("engine params restored from peer "
                                "snapshot step %d", pulled_step)
                except Exception as e:  # noqa: BLE001 - template mismatch
                    params = None
                    logger.warning(
                        "pulled snapshot is not an engine param tree "
                        "(%s); cold init instead", e)
        except WeightStreamError as e:  # pragma: no cover - network path
            logger.warning("weight pull failed, cold start: %s", e)

    engine = InferenceEngine(
        cfg, params=params, batch_size=args.batch_size,
        max_len=args.max_len, quantize=args.quantize, mesh=mesh,
        paged=args.paged or args.prefix_cache,
        kv_block_size=args.kv_block_size,
        total_kv_blocks=args.total_kv_blocks,
        prefix_cache=args.prefix_cache,
        kv_quantize=args.kv_quantize,
        # sweep-tuned default (engine ctor None means DISABLED, so the
        # resolution lives here); --prefill-chunk 0 opts out
        prefill_chunk=(InferenceEngine.TUNED_PREFILL_CHUNK
                       if args.prefill_chunk is None
                       else (args.prefill_chunk or None)),
        telemetry=None if args.no_telemetry else make_engine_telemetry(),
        compile_cache=compile_cache,
    )
    seed_rate = args.seed_rate_bps or float(
        _os.environ.get("DSTACK_SEED_RATE_BPS", "0") or 0)
    serving = ServingApp(engine, tokenizer, model_name=model_name,
                         snapshot_dir=args.snapshot_dir,
                         standby=args.standby, seed_rate_bps=seed_rate)
    # a standby warms before it will ever see traffic; a normal replica
    # warms too when a compile cache is configured (cheap on a hit, and
    # it fills the cache for the fleet on a miss)
    serving.start_engine(warm=args.standby or compile_cache is not None)
    web.run_app(serving.make_app(), host="0.0.0.0", port=args.port)


if __name__ == "__main__":
    main()
