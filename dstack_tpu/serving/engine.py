"""Continuous-batching inference engine (JetStream-style): the scheduler.

A fixed pool of decode *slots* shares one model replica's device state;
prefill computes a prompt's state with the full forward pass and inserts it
into a free slot; decode advances ALL active slots one token per step with
per-slot positions. Static shapes throughout (prompt lengths padded to
buckets) so both phases jit-compile once and stay on the MXU.

This module owns slots, KV blocks, queues, decode windows, sampling,
telemetry, the compile cache and the order of dispatch.  What the model's
memory IS and what a layer computes belong to the family's provider
(``serving/families.py`` picks it: ``serving/dense.py`` for the Llama
family, ``serving/hybrid.py`` for the KDA + MLA hybrid): it allocates the
two donated state trees this module hands to every program as they are,
builds the functions this module names, jits and dispatches, and records
what its decode windows count about themselves.

No reference equivalent — the reference proxies to SGLang/TGI
(gateway/services/model_routers/sglang.py); this engine is the TPU-native
backend those services run on.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import queue
import threading
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.elastic.compile_cache import CompileCache, maybe_cached
from dstack_tpu.serving.families import programs_for
from dstack_tpu.serving.paging import BlockAllocator, PrefixBlockAllocator
from dstack_tpu.utils.jax_runtime import named_jit

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)

#: flags of a pending slot update (``InferenceEngine._flush_slot_updates``):
#: the slot decodes; its last token is written; with the token the host
#: gives, not the one the sampler left on the device
_ACTIVE, _TOKEN, _TOKEN_FROM_HOST = 1, 2, 4

logger = logging.getLogger(__name__)


class EngineDraining(RuntimeError):
    """Raised by :meth:`InferenceEngine.submit` once the engine is in
    drain mode: in-flight requests finish, new ones must go elsewhere
    (the HTTP layer answers 503 + Retry-After before this can fire)."""


@dataclasses.dataclass
class Request:
    tokens: List[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_p: float = 1.0
    #: keep only the k highest-probability tokens before nucleus masking
    #: (0 = disabled).  Applied inside the fused on-device sampler, so it
    #: costs nothing extra on the decode hot loop.
    top_k: int = 0
    eos_id: Optional[int] = None
    #: called with each generated token id (streaming); None = collect only
    on_token: Optional[Callable[[int], None]] = None
    #: PD disaggregation: KV produced by a PREFILL replica
    #: ({"ks": np [L,n,Hkv,D], "vs": np, "first_token": int, "length": int});
    #: when set, admission installs the KV instead of running prefill
    prefill: Optional[dict] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    finish_reason: str = ""
    #: wall clock; the later stamps are this plus monotonic time (now())
    submitted_at: float = dataclasses.field(default_factory=time.time)
    #: when the request claimed a slot (queue wait = admitted - submitted)
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    #: set via cancel(); the engine releases the slot at the next emit
    #: (queued requests finish without ever occupying one)
    cancelled: bool = False
    #: absolute wall-clock deadline (``time.time()``; from the inbound
    #: ``X-Dstack-Deadline`` budget).  Expired-in-queue requests are
    #: evicted at admission WITHOUT burning a prefill; an expired decode
    #: is cancelled at the next emit and its slot/KV blocks freed.
    deadline: Optional[float] = None
    #: distributed-tracing context (telemetry/tracing.py): when set, the
    #: telemetry layer derives engine spans from this request's scheduler
    #: stamps at finish and attaches the trace id as a histogram exemplar
    trace_id: Optional[str] = None
    parent_span_id: Optional[str] = None
    #: ``perf_counter`` reading taken with ``submitted_at`` (see :meth:`now`)
    _submitted_perf: float = dataclasses.field(
        default_factory=time.perf_counter, repr=False)

    def now(self) -> float:
        """The stamp for every scheduler event after submission:
        ``submitted_at`` (wall clock, the anchor cross-process traces
        line up on) plus the MONOTONIC time elapsed since, so differences
        of two stamps never go negative when the wall clock is stepped."""
        return self.submitted_at + (time.perf_counter()
                                    - self._submitted_perf)

    def cancel(self, reason: str = "cancelled") -> None:
        """Stop generating for this request as soon as the engine next
        looks at it (stop-sequence hit, client disconnect, ...).  Safe to
        call from any thread; already-finished requests are unaffected."""
        if not self.finish_reason:
            self.finish_reason = reason
        self.cancelled = True


class _Phase:
    """One phase of the engine's loop, as a context manager: the
    ``engine.<phase>`` span on the profiler's clock (the device trace's
    own; ``args`` say whose span it is and are formatted only while a
    trace is taken) and, with telemetry on, the phase's count and its SELF
    seconds on ``time.perf_counter`` (``EngineTelemetry.record_phase``).

    A phase opened inside another pauses it.  The engine's stack holds one
    float a phase that is open: the seconds of self time so far while the
    phase is paused, and ``now`` less those seconds while it runs, so a
    boundary is one clock reading that ends one phase's time and starts
    the other's.  Single writer (the engine's thread): no lock."""

    __slots__ = ("_span", "_telemetry", "_stack", "_phase")

    def __init__(self, engine: "InferenceEngine", phase: str, args: dict):
        self._span = jax.profiler.TraceAnnotation("engine." + phase, **args)
        self._telemetry = engine.telemetry
        self._stack = engine._phase_stack
        self._phase = phase

    def __enter__(self) -> None:
        if self._telemetry is not None:
            now = time.perf_counter()
            stack = self._stack
            if stack:
                stack[-1] = now - stack[-1]     # pause the parent
            stack.append(now)
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        if self._telemetry is not None:
            now = time.perf_counter()
            stack = self._stack
            self._telemetry.record_phase(self._phase, now - stack.pop())
            if stack:
                stack[-1] = now - stack[-1]     # the parent runs again


class InferenceEngine:
    """Slot-based continuous batching over one model replica.

    batch_size slots share the family's device state (for the Llama family
    a [L, B, max_len, Hkv, D] cache or a paged pool); `step()` is one
    scheduling iteration: admit waiting prompts into free slots (prefill),
    then advance every active slot a WINDOW of tokens in one dispatch
    (the provider's ``decode_window_fn``) with on-device nucleus sampling.
    Streaming callbacks therefore arrive in bursts of up to
    `DECODE_WINDOWS[-1]` tokens, and a queued prompt waits at most one
    window for a free slot — the price of amortizing the host round-trip
    across the window.  The first tokens of one admission pass arrive
    together at its end for the same reason: the pass sends every prompt's
    program back to back and pulls their first tokens once (see
    :meth:`_hand_over_first_tokens`).
    """

    #: Chunked-prefill sweep winner (PR 18): chunk=512 held background
    #: decode within range of smaller chunks at the best arrival TTFT.
    #: tests/compute/test_serving_decode.py pins it so a default change is
    #: a deliberate re-sweep, not drift.
    TUNED_PREFILL_CHUNK = 512

    def __init__(
        self,
        cfg: Any,
        params: Optional[Any] = None,
        batch_size: int = 8,
        max_len: int = 1024,
        rng_seed: int = 0,
        paged: bool = False,
        kv_block_size: int = 32,
        total_kv_blocks: Optional[int] = None,
        quantize: Optional[str] = None,
        kv_quantize: Optional[str] = None,
        mesh: Optional[Any] = None,
        sharding_policy: Optional[Any] = None,
        prefix_cache: bool = False,
        prefill_chunk: Optional[int] = None,
        telemetry: Optional[Any] = None,
        compile_cache: Optional[CompileCache] = None,
    ) -> None:
        """`paged=True` switches the KV cache from a dense [B, max_len] row
        per slot to block paging (serving/paging.py): each request reserves
        only ceil((prompt + max_new) / block) blocks at admission, so
        `total_kv_blocks` can be far below batch_size * max_len / block when
        typical requests are shorter than max_len.  Admission blocks (the
        request waits queued) when the pool is exhausted — never mid-decode.

        ``prefix_cache=True`` (paged mode only) reuses the KV of shared
        prompt prefixes across requests: full prompt blocks register under
        content-chained keys after prefill; a later prompt that starts with
        the same blocks skips recomputing them and prefills only its suffix
        (serving/paging.py PrefixBlockAllocator — the vLLM automatic-
        prefix-caching analog).  Wins are proportional to shared-prefix
        length: system prompts, few-shot preambles, chat history.

        ``kv_quantize="int8"`` stores the KV cache as int8 with one f32
        scale per (token, head) row (serving/quant.py quantize_kv) —
        attention is KV-read-bound at high concurrency, and int8 halves
        those bytes; the dequant fuses into the attention dots so int8 is
        what crosses HBM.  ~0.6% RMS error per row; short greedy
        continuations match the exact engine in tests.  Composes with
        weight int8, paging, prefix caching, and mesh TP.
        ``kv_quantize="int4"`` packs two values per byte (quantize_kv4),
        quartering the KV bytes and doubling the resident slot count a
        paged pool can hold vs int8 — at ~6% RMS row error, so it is
        opt-in for deployments that tolerate the drift (the accuracy
        trade-off is documented in docs/concepts/services.md).

        ``prefill_chunk``: prompts longer than this prefill in chunks of at
        most this many tokens, interleaved with decode windows: each
        scheduling step spends a budget of at most ``batch_size`` chunks
        ahead of its window, on the oldest-admitted prompt first and each
        prompt to its end before the next — a long prompt no longer stalls
        every active decode slot for its whole prefill, and a burst of long
        prompts stalls them for ``batch_size * prefill_chunk`` prompt
        tokens a window at most.  The admitted slot stays inactive until
        its last chunk completes and produces the first token; it decodes
        in the very next window.  Works on dense and
        paged caches (paged chunks ride the suffix-prefill block
        scatter/gather and COMPOSE with prefix caching: a reused prefix
        skips its chunks entirely).  None disables (whole-prompt prefill
        at admission).

        ``telemetry``: a `dstack_tpu.telemetry.serving.EngineTelemetry`
        recording queue-wait/TTFT/inter-token histograms, batch occupancy,
        KV utilization and preemptions from the
        scheduler thread (serving/server.py exposes it on /metrics and
        /stats).  None (the default) disables recording entirely: the hot
        paths pay a single ``is None`` check and ``_emit`` allocates
        nothing extra per token.

        ``mesh``: a `jax.sharding.Mesh` for multi-chip tensor-parallel
        serving — models too big for one chip's HBM (8B bf16+KV, 70B).
        Params shard Megatron-style (heads/FFN columns over the tensor
        axis, row-parallel projections psum'd by XLA) and the KV cache
        shards over KV heads; the engine's math is unchanged — GSPMD
        partitions the same jitted functions from the input placements.
        Defaults to TP-only placement; pass ``sharding_policy`` (a
        `models.llama.ShardingPolicy`) to override.  Requires num_kv_heads
        % tensor degree == 0.  MoE models additionally shard their experts
        over an ``expert`` mesh axis when present (num_experts must divide
        its degree) — GSPMD inserts the dispatch/combine resharding.
        ``compile_cache``: a `dstack_tpu.elastic.compile_cache.CompileCache`
        consulted before every jit lowering — a scaling-up replica whose
        programs a peer already compiled deserializes them in
        milliseconds instead of compiling them.  Defaults to the
        env-configured cache
        (``DSTACK_COMPILE_CACHE`` / ``DSTACK_COMPILE_CACHE_PEERS``);
        both unset → no caching, the plain jit path.  Hit/miss counters
        surface on ``/load`` and ``/stats``.
        """
        self.cfg = cfg
        self.telemetry = telemetry
        self.compile_cache = (compile_cache if compile_cache is not None
                              else CompileCache.from_env())
        self.batch_size = batch_size
        self.max_len = min(max_len, cfg.max_seq_len)
        self.paged = paged
        #: paged decode reads only a power-of-two BUCKET of each slot's
        #: block table sized to the longest active slot (ragged lengths),
        #: instead of the full blocks_per_slot span; DSTACK_TPU_RAGGED_DECODE=0
        #: restores the full-span gather (the dense-paged bench baseline)
        self._ragged = os.environ.get(
            "DSTACK_TPU_RAGGED_DECODE", "1") != "0"
        n_blocks = 0
        if paged:
            if kv_block_size <= 0 or kv_block_size & (kv_block_size - 1):
                # buckets are powers of two: any power-of-two block size
                # tiles them exactly (after rounding the bucket up to one
                # block, see _bucket)
                raise ValueError("kv_block_size must be a power of two")
            if self.max_len % kv_block_size:
                raise ValueError("max_len must be a multiple of kv_block_size")
            self._block_size = kv_block_size
            self._blocks_per_slot = self.max_len // kv_block_size
            n_blocks = (total_kv_blocks if total_kv_blocks is not None
                        else batch_size * self._blocks_per_slot + 1)
            if n_blocks <= self._blocks_per_slot:
                # a max-size request must always be admittable on an idle
                # engine, or the head-of-line stall never resolves
                raise ValueError(
                    f"total_kv_blocks must exceed {self._blocks_per_slot} "
                    f"(= max_len / kv_block_size)")
        #: the family's provider (serving/families.py): what the device
        #: state is, and the functions of the programs dispatched below.
        #: It refuses the options its model is not served with.
        self._programs = programs_for(
            cfg, batch_size=batch_size, max_len=self.max_len, paged=paged,
            block_size=kv_block_size, num_blocks=n_blocks,
            prefix_cache=prefix_cache, quantize=quantize,
            kv_quantize=kv_quantize, mesh=mesh,
            sharding_policy=sharding_policy, sample=self._sample_on_device)
        cache_layers, token_bytes = self._programs.kv_geometry()
        if telemetry is not None:
            telemetry.record_kv_geometry(cache_layers, token_bytes)
        if paged:
            self._alloc = (PrefixBlockAllocator(n_blocks) if prefix_cache
                           else BlockAllocator(n_blocks))
            # The pool in the units it is sized in.  Far fewer tokens than
            # the slots could ask for is a choice (requests shorter than
            # max_len) whose price is said with it: admission waits on the
            # pool, and a decode path without the block-table kernel still
            # gathers a [cache layers, B, span] linear view of its own.
            tokens = (n_blocks - 1) * kv_block_size  # block 0 is NULL
            asked = batch_size * self.max_len
            sized = ("paged KV pool: %d blocks of %d hold %d tokens in "
                     "%.2f GB (%d B a token over %d cache layers); %d slots "
                     "at max_len %d could ask for %d")
            facts = (n_blocks, kv_block_size, tokens,
                     n_blocks * kv_block_size * token_bytes / 1e9,
                     token_bytes, cache_layers, batch_size, self.max_len,
                     asked)
            if tokens < asked // 2:
                logger.warning(
                    sized + ": the pool, not the slot count, bounds the "
                    "batch, and a decode path that gathers a linear view "
                    "(no TPU kernel, int4 pages) holds up to %.2f GB beside "
                    "it", *facts, asked * token_bytes / 1e9)
            else:
                logger.info(sized, *facts)
            self._tables_host = np.zeros(
                (batch_size, self._blocks_per_slot), np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(batch_size)]
        if prefill_chunk is not None and prefill_chunk < 1:
            # 0 would make every request chunk forever on empty slices
            raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = prefill_chunk
        #: slot_id -> {"tokens", "done", ("logits", "n")} for prompts
        #: mid-chunked-prefill (see prefill_chunk)
        self._chunking: dict = {}
        self.prefix_cache = prefix_cache
        #: per-slot (prefix_len, block_keys) staged between reserve and
        #: prefill (prefix-cache mode)
        self._slot_prefix: List[tuple] = [(0, []) for _ in range(batch_size)]
        self.params = self._programs.prepare_params(params, rng_seed)
        self._queue: "queue.Queue[Request]" = queue.Queue()
        #: head-of-line request waiting for KV blocks (paged mode)
        self._stalled: Optional[Request] = None
        self._slots: List[Optional[Request]] = [None] * batch_size

        self._reset_device_state()

        #: compile-cache tags (and so a trace's module names) of the two
        #: prompt programs: they say which cache the program writes
        self._prefill_tag, self._chunk_tag = (
            ("prefill_paged_b", "prefill_prefix_b") if paged
            else ("prefill_b", "prefill_chunk_b"))
        self._prefill_jit = {}
        self._decode_jit = {}  # (window, sampling) -> jitted K-step decode
        self._rng_key = jax.random.PRNGKey(rng_seed)
        self._stop = False
        #: drain mode: finish in-flight work, refuse new submissions
        #: (replica drain-and-migrate — serving/server.py /drain)
        self.draining = False
        #: request mid-admission: popped from the queue but its prefill
        #: (seconds, under compile) not yet done assigning a slot — without
        #: this, has_work()/drained would call the replica idle in exactly
        #: that window and an orchestrator could tear it down mid-admission
        self._admitting: Optional[Request] = None
        #: bumped on any slot-assignment change; keys the cached per-window
        #: device constants in _decode (see _decode_consts)
        self._slots_gen = 0
        self._decode_consts = None
        #: in-flight decode window (see step): {tokens, window,
        #: remaining_after} or None
        self._pending = None
        #: engine watchdog (grey-failure defense): a scheduling step that
        #: has been stuck past this window means the device runtime is
        #: wedged — the HTTP layer fails /load and /health so routers and
        #: orchestrators stop sending work instead of hanging on it
        self._watchdog_s = float(os.environ.get(
            "DSTACK_TPU_ENGINE_WATCHDOG_S", "300"))
        self._step_started_at: Optional[float] = None
        #: the open phases' clocks, innermost last (see :class:`_Phase`)
        self._phase_stack: List[float] = []
        #: decode windows dispatched so far: the ``window`` argument that
        #: joins a window's dispatch, pull and emit spans
        self._window_seq = 0

    def _phase(self, phase: str, **args) -> _Phase:
        """The context of one phase of the loop (``telemetry.PHASES``)."""
        return _Phase(self, phase, args)

    def _reset_device_state(self) -> None:
        """(Re-)allocate the family's device state and the slot state.
        Called at init and after a device-side decode failure (the decode
        jit donates the state, so a raise mid-execution leaves it
        deleted)."""
        b = self.batch_size
        # host-only records first: they must not outlive the requests the
        # crash handler failed, even when the allocations below raise
        #: slot -> [length, flags, token]: the net state of every slot
        #: written since the last flush (see _flush_slot_updates)
        self._slot_updates: dict = {}
        #: (slot, request, token or None) in admission order: requests
        #: whose first token is still on the device (None: in
        #: ``_first_tokens``) or came over the PD wire
        self._first_pending: List[tuple] = []
        #: the two donated trees every program takes and returns: what they
        #: hold is the provider's (a K and a V cache; a latent pool and a
        #: recurrent state)
        self._state = self._programs.init_state()
        recurrent = self._programs.recurrent_state_bytes()
        if self.telemetry is not None and recurrent:
            self.telemetry.record_recurrent_state_bytes(recurrent)
        if self.paged and isinstance(self._alloc, PrefixBlockAllocator):
            # the KV backing every cached key was just reallocated
            self._alloc.clear_cache()
        self._decode_consts = None  # cached device constants died with it
        self._pending = None        # in-flight window handles died with it
        self._chunking = {}         # mid-chunk prefill state died with it
        self._lengths = jnp.zeros((b,), jnp.int32)     # tokens in cache
        # host mirror of _lengths: _emit's bookkeeping must not pay a
        # device->host fetch per generated token
        self._host_lengths = np.zeros((b,), np.int64)
        self._last_token = jnp.zeros((b,), jnp.int32)
        self._active = jnp.zeros((b,), jnp.bool_)
        #: where the sampler leaves a request's first token until its pass
        #: ends (see _sample_first)
        self._first_tokens = jnp.zeros((b,), jnp.int32)

    # -- public API --------------------------------------------------------

    def submit(self, request: Request) -> Request:
        if self.draining:
            # belt for non-HTTP callers; the server's handlers 503 first
            raise EngineDraining("engine is draining; not admitting")
        if request.prefill is not None and self._programs.pd_refusal:
            raise ValueError(self._programs.pd_refusal)
        # clamp so prompt + generation always fit the cache
        request.max_new_tokens = max(min(request.max_new_tokens,
                                         self.max_len - 2), 1)
        self._queue.put(request)
        if self.telemetry is not None:
            self.telemetry.record_queue_depth(self._queue.qsize())
        return request

    def generate(self, tokens: List[int], **kw) -> Request:
        """Blocking helper: submit + run the loop until this request is done
        (single-threaded use / tests)."""
        req = Request(tokens=tokens, **kw)
        self.submit(req)
        while not req.done.is_set():
            self.step()
        return req

    def warmup(self, prompt_len: int = 8, max_new_tokens: int = 4) -> float:
        """Drive one tiny request end-to-end so the smallest prefill
        bucket and the decode window are compiled (or pulled from the
        compile cache) before real traffic arrives — the standby pool's
        warming step (elastic/standby.py) and the cold-start bench's
        warmup leg.  Returns elapsed seconds."""
        t0 = time.time()
        self.generate(list(range(1, prompt_len + 1)),
                      max_new_tokens=max_new_tokens)
        return time.time() - t0

    def run_forever(self) -> None:
        """Serving loop: step when there is work, block when idle. A bad
        request must not kill the engine thread (every later request would
        hang) — fail the in-flight requests and keep serving."""
        while not self._stop:
            if not self.has_work():
                try:
                    with self._phase("wait_for_work"):
                        req = self._queue.get(timeout=0.05)
                    self._queue.put(req)
                except queue.Empty:
                    continue
            try:
                self.step()
            except Exception:  # noqa: BLE001
                import traceback

                traceback.print_exc()
                # fail only the requests that were actually in flight
                # (queued-but-unscheduled requests get their own attempt)
                # using HOST state only — _release's device updates could
                # themselves raise against a wedged runtime
                for slot_id, req in enumerate(self._slots):
                    if req is not None:
                        self._release_host(slot_id)
                        req.finish_reason = "error"
                        req.finished_at = req.now()
                        req.done.set()
                        if self.telemetry is not None:
                            self.telemetry.record_preemption("engine_error")
                            self.telemetry.record_finished(req)
                # the decode jit donates the state: if it raised after
                # donation, self._state points at deleted buffers and
                # every later request would die — reallocate device state
                try:
                    self._reset_device_state()
                except Exception:  # noqa: BLE001 — runtime truly dead
                    traceback.print_exc()
                    # run_forever owns its dedicated engine thread
                    # (ServingApp.start_engine)  # dtlint: disable=DT103
                    time.sleep(0.5)  # don't spin hot; retry on next step

    def stop(self) -> None:
        self._stop = True

    def begin_drain(self) -> None:
        """Enter drain mode: stop admitting, keep decoding what's in
        flight.  Idempotent; the engine thread keeps running so accepted
        streams complete — callers poll :attr:`drained` (or the replica's
        ``/load``) to learn when teardown is safe."""
        self.draining = True

    def end_drain(self) -> None:
        """Leave drain mode (aborted migration, maintenance over): the
        replica admits new work again, warm caches intact.  Idempotent —
        and without it a stray ``/drain`` would stop a healthy replica
        until a process restart."""
        self.draining = False

    @property
    def drained(self) -> bool:
        """True once drain mode is on and no request is queued, admitted,
        or mid-dispatch — the replica can be torn down with zero drops."""
        return self.draining and not self.has_work()

    def has_work(self) -> bool:
        return (any(s is not None for s in self._slots)
                or self._pending is not None or bool(self._chunking)
                or self._stalled is not None or self._admitting is not None
                or not self._queue.empty())

    # -- scheduling --------------------------------------------------------

    @property
    def wedged(self) -> bool:
        """True when ONE scheduling step has been stuck longer than the
        watchdog window: a device dispatch that never returns (hung
        runtime, deadlocked collective).  Read from the HTTP thread —
        the engine thread itself is the thing that is stuck, so the
        detection must live outside it.  `serving/server.py` fails
        ``/load`` and ``/health`` on it, so callers stop routing here
        instead of every request hanging to its deadline."""
        t0 = self._step_started_at
        return t0 is not None and time.time() - t0 > self._watchdog_s

    def step(self) -> None:
        """One scheduling iteration (see :meth:`_step`), stamped for the
        wedge watchdog: ``_step_started_at`` is live for exactly the
        span of one step, so a step that never returns is visible to the
        HTTP thread as :attr:`wedged`."""
        self._step_started_at = time.time()
        try:
            self._step()
        finally:
            self._step_started_at = None

    def _step(self) -> None:
        """One scheduling iteration, software-pipelined over the device.

        A decode window's outputs are device handles; the NEXT window needs
        only those handles, not the tokens.  So when a window is in flight,
        the next one is dispatched BEFORE the current one's tokens are
        pulled to the host — the np.asarray round-trip and the Python emit
        loop overlap device compute.

        Admission (prefill) only ever happens when NO window is in flight:
        a prefill writes cache rows that an in-flight window's end-of-window
        bulk insert could clobber.  The overlap chain therefore breaks
        whenever a queued request could take a free slot, costing one
        non-overlapped window at request boundaries.  An admission pass
        sends every prompt's program and sampler back to back and waits
        for the device once, at its end (:meth:`_hand_over_first_tokens`);
        what the pass and a drain change in the slots' device state goes
        out as one program each (:meth:`_flush_slot_updates`).

        Chunked prompts are advanced ahead of the step's window, on a
        budget (see :meth:`_advance_chunks`).  The chain also breaks on a
        step whose chunks complete a prompt: the in-flight window is
        drained first (the chunks run on the device meanwhile), the prompt
        is activated, and only then is the next window dispatched, so the
        new slot decodes in it instead of riding it out as junk.
        """
        # chunks this step may still put ahead of its window
        budget = self.batch_size
        if self._pending is not None:
            nxt, broke = None, "admission"
            if not self._can_admit():
                # chunks chain on the donated cache behind the in-flight
                # window, and ahead of nxt
                spent, completed = self._advance_chunks(budget)
                budget -= spent
                broke = "prompt_completed"
                if not completed:
                    nxt = self._dispatch_window(
                        self._pending["remaining_after"])
                    broke = None if nxt is not None else "drained"
            if self.telemetry is not None:
                self.telemetry.record_window_chain(broke)
            self._drain_window()
            self._finish_chunked()
            self._pending = nxt
            if nxt is not None:
                return
        self._admit()
        self._advance_chunks(budget)
        self._finish_chunked()
        decoding = [
            req for slot_id, req in enumerate(self._slots)
            if req is not None and slot_id not in self._chunking]
        if decoding:
            remaining = max(
                req.max_new_tokens - len(req.output) for req in decoding)
            self._pending = self._dispatch_window(remaining)

    def _advance_chunks(self, budget: int) -> tuple:
        """Dispatch prefill chunks, the oldest-admitted prompt's first and
        each prompt to its end before the next one starts (a finished
        prompt is a slot that decodes), until no mid-chunking slot has a
        chunk left or ``budget`` chunks went out.  The budget bounds the
        stall one scheduling step can put ahead of its decode window: a
        step starts with ``batch_size`` chunks and its second call gets
        what the first left.  Returns (chunks dispatched, whether some
        prompt's last chunk was among them)."""
        if budget <= 0:  # spent by the step's first call, which counted that
            return 0, False
        spent, completed = 0, False
        # dict order is admission order: a slot is keyed when it is claimed
        for slot_id, st in list(self._chunking.items()):
            # a state with logits is complete: _finish_chunked's
            while "logits" not in st and spent < budget:
                req = self._slots[slot_id]
                if req is None or req.cancelled:
                    del self._chunking[slot_id]
                    if req is not None:
                        self._release(slot_id)
                        req.finish_reason = req.finish_reason or "cancelled"
                        req.finished_at = req.now()
                        req.done.set()
                        if self.telemetry is not None:
                            self.telemetry.record_finished(req)
                    break
                with self._phase("chunk", slot=slot_id, tokens=min(
                        self.prefill_chunk, len(st["tokens"]) - st["done"])):
                    self._dispatch_chunk(slot_id, st)
                spent += 1
                completed = completed or "logits" in st
        if self.telemetry is not None and spent:
            self.telemetry.record_prefill_chunks(
                spent, first_of_step=budget == self.batch_size,
                budget_exhausted=(spent == budget
                                  and self._chunk_backlog() > 0))
        return spent, completed

    def _dispatch_chunk(self, slot_id: int, st: dict) -> None:
        """Dispatch the next prefill chunk of one mid-chunking slot."""
        tokens, done = st["tokens"], st["done"]
        chunk = tokens[done:done + self.prefill_chunk]
        cbucket = self._bucket(len(chunk))
        padded = np.zeros((cbucket,), np.int32)
        padded[:len(chunk)] = chunk
        logits = self._run_chunk(slot_id, padded, len(chunk), done)
        st["done"] = done + len(chunk)
        if self.telemetry is not None:
            self.telemetry.record_prefill(len(chunk), cbucket)
            self._programs.record_prompt_program(self.telemetry, cbucket)
            # keep the backlog gauge fresh even when every slot is
            # chunking (no decode window dispatches then)
            self.telemetry.record_prefill_backlog(self._chunk_backlog())
        if st["done"] >= len(tokens):
            st["logits"] = logits
            st["n"] = len(tokens)

    def _finish_chunked(self) -> None:
        """Activate slots whose final prefill chunk has completed: sample
        the first token from the chunk's logits and open the slot for
        decode windows (it joins the next dispatched window).  The prompts
        one step completed are activated together: their samplers go out
        back to back and their first tokens come over in one pull."""
        completed = [slot_id for slot_id, st in self._chunking.items()
                     if "logits" in st]
        if not completed:
            return
        # the span that activates the completed prompts: no chunk goes
        # out under it
        with self._phase("chunk", requests=len(completed), tokens=0):
            for slot_id in completed:
                st = self._chunking.pop(slot_id)
                req = self._slots[slot_id]
                if req is None:
                    continue
                n = st["n"]
                if self.prefix_cache:
                    # publish the completed prompt's full blocks for future
                    # prefix reuse (mirrors _prefill's publication)
                    blocks = self._slot_blocks[slot_id]
                    for i, bkey in enumerate(self._slot_prefix[slot_id][1]):
                        if ((i + 1) * self._block_size <= n
                                and i < len(blocks)):
                            self._alloc.register(bkey, blocks[i])
                self._activate(slot_id, req, n, st["logits"])
            self._hand_over_first_tokens()

    def _can_admit(self) -> bool:
        """A waiting request could take a free slot."""
        return ((self._stalled is not None or not self._queue.empty())
                and any(s is None for s in self._slots))

    def _admit(self) -> None:
        """Admit queued requests into free slots, under one
        ``engine.admit`` span per call that has both.  The pass sends every
        prompt's program and sampler back to back; its slot updates and
        its one wait for the device close it, under an ``engine.prefill``
        span of no tokens (the closing span shows in a trace whose edge
        dropped the pass's long ``engine.admit``)."""
        if not self._can_admit():
            return
        with self._phase("admit"):
            self._admit_into_free_slots()
            if self._first_pending:
                with self._phase("prefill",
                                 requests=len(self._first_pending), tokens=0):
                    self._hand_over_first_tokens()

    def _admit_into_free_slots(self) -> None:
        for slot_id in range(self.batch_size):
            if self._slots[slot_id] is not None:
                continue
            req = self._stalled
            self._stalled = None
            if req is None:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    return
            # visible to has_work() for the whole admission (prefill can
            # spend seconds compiling before the slot is claimed)
            self._admitting = req
            try:
                if (not req.cancelled and req.deadline is not None
                        and time.time() > req.deadline):
                    # expired while queued (or stalled at head-of-line):
                    # evict with the honest reason BEFORE burning a
                    # prefill on an answer nobody is waiting for
                    req.cancel(reason="deadline")
                if req.cancelled:
                    # cancelled while queued: finish without taking the slot
                    req.finish_reason = req.finish_reason or "cancelled"
                    req.finished_at = req.now()
                    req.done.set()
                    if self.telemetry is not None:
                        self.telemetry.record_finished(req)
                    continue
                if self.paged and not self._reserve_blocks(slot_id, req):
                    # pool exhausted: hold at head of line until a release
                    # frees blocks (all-at-admission allocation means decode
                    # itself can never stall)
                    if (self.telemetry is not None
                            and not getattr(req, "_stall_counted", False)):
                        # once per request, however many steps it stays
                        # stalled
                        req._stall_counted = True
                        # stall start for the engine.kv_wait trace span
                        req._kv_stalled_at = req.now()
                        self.telemetry.record_preemption(
                            "kv_blocks_exhausted")
                    self._stalled = req
                    return
                if self.paged and self.telemetry is not None:
                    # the pool grows here, not at dispatch: a request that
                    # comes and goes between two windows still shows in
                    # the peak
                    self.telemetry.record_kv_utilization(
                        self._kv_used_fraction())
                try:
                    n = self._prompt_len(req)
                    if req.prefill is not None:
                        with self._phase("prefill", slot=slot_id, tokens=n):
                            self._insert_prefilled(slot_id, req)
                    elif (self.prefill_chunk is not None
                          and n > self.prefill_chunk):
                        # long prompt: claim the slot now, prefill in chunks
                        # on the steps' budget (interleaved with decode
                        # windows); the slot stays inactive until the last
                        # chunk yields the first token.  A prefix-cache hit
                        # starts past the reused rows — its chunks are
                        # skipped, not recomputed.
                        tokens = self._prompt_tokens(req.tokens,
                                                     req.max_new_tokens)
                        done = (self._slot_prefix[slot_id][0]
                                if self.prefix_cache else 0)
                        self._slots[slot_id] = req
                        self._slots_gen += 1
                        self._mark_admitted(req)
                        self._chunking[slot_id] = {"tokens": tokens,
                                                   "done": done}
                    else:
                        with self._phase("prefill", slot=slot_id, tokens=n):
                            self._prefill(slot_id, req)
                except Exception:
                    # claim the slot so the crash handler (run_forever)
                    # fails this request and releases its KV-block
                    # reservation — otherwise a prefill-time device error
                    # drops the request silently and leaks the blocks
                    if self._slots[slot_id] is None:
                        self._slots[slot_id] = req
                        self._slots_gen += 1  # cached decode consts stale
                    raise
            finally:
                self._admitting = None

    def _mark_admitted(self, req: Request) -> None:
        """Stamp slot admission and record the queue wait (once — retried
        admissions after a device error keep the first stamp)."""
        if req.admitted_at is None:
            req.admitted_at = req.now()
            if self.telemetry is not None:
                self.telemetry.record_admitted(
                    req.admitted_at - req.submitted_at,
                    trace_id=req.trace_id)

    def _prompt_tokens(self, tokens: List[int],
                       max_new_tokens: int) -> List[int]:
        """Prompt tokens that survive the cache budget clamp (the single
        source of truth shared by prefill, PD export and block sizing)."""
        budget = max(self.max_len - max_new_tokens - 1, 1)
        return list(tokens[-budget:]) or [0]

    def _prompt_len(self, req: Request) -> int:
        if req.prefill is not None:
            return min(int(req.prefill["length"]), self.max_len - 2)
        return len(self._prompt_tokens(req.tokens, req.max_new_tokens))

    def _reserve_blocks(self, slot_id: int, req: Request) -> bool:
        n = self._prompt_len(req)
        bs = self._block_size
        need = -(-(n + req.max_new_tokens + 1) // bs)
        matched: List[int] = []
        keys: List = []
        if (self.prefix_cache and req.prefill is None):
            tokens = self._prompt_tokens(req.tokens, req.max_new_tokens)
            keys = PrefixBlockAllocator.block_keys(tokens, bs)
            # cap the reuse so at least one suffix token remains — the
            # prefill must still produce last-position logits
            matched = self._alloc.lookup(keys[: (n - 1) // bs])
        prefix_len = len(matched) * bs
        if req.prefill is None:
            # colocated prefill writes a whole padded bucket (past the
            # reused prefix, in prefix-cache mode)
            need = max(need,
                       (prefix_len + self._bucket(n - prefix_len)) // bs)
        need = min(need, self._blocks_per_slot)
        # dtlint: transfers=kv-blocks (the engine owns them: stored in
        # _slot_blocks and freed by _release_host on slot teardown)
        fresh = self._alloc.alloc(need - len(matched))
        if fresh is None:
            if matched:
                self._alloc.release(matched)  # undo the lookup refs
            return False
        blocks = matched + fresh
        self._slot_blocks[slot_id] = blocks
        self._slot_prefix[slot_id] = (prefix_len, keys)
        self._tables_host[slot_id, :] = 0
        self._tables_host[slot_id, :need] = blocks
        return True

    def _bucket(self, n: int) -> int:
        for b in PREFILL_BUCKETS:
            if n <= b and b <= self.max_len:
                bucket = b
                break
        else:
            bucket = self.max_len
        if self.paged:
            # a prefill bucket must span whole blocks
            bucket = max(bucket, self._block_size)
        return bucket

    def _jit_cached(self, fn, tag: str, **jit_kwargs):
        """Jit ``fn`` under its compile-cache tag (so a device trace names
        the program ``jit_<tag>``) and route it through the persistent
        compile cache (no-op passthrough when the cache is disabled)."""
        return maybe_cached(named_jit(fn, tag, **jit_kwargs),
                            self.compile_cache, tag=tag)

    def _run_program(self, table: dict, key, make, *args):
        """Call the program ``table[key]`` on ``args``, building it with
        ``make()`` on first use.  A jitted function traces, lowers and
        compiles (or loads from a cache) inside its first call, so the
        build and that call sit under one ``engine.build_program`` span:
        inside serving it is a live request that hit a new shape."""
        fn = table.get(key)
        if fn is not None:
            return fn(*args)
        with self._phase("build_program"):
            fn = table[key] = make()
            if self.telemetry is not None:
                self.telemetry.record_program_built(
                    "decode" if table is self._decode_jit else "prefill")
            return fn(*args)

    def _prefill_program(self, bucket: int):
        """The jitted whole-prompt prefill of one bucket."""
        return self._jit_cached(self._programs.prefill_fn(bucket),
                                f"{self._prefill_tag}{bucket}",
                                donate_argnums=(3, 4))

    def _chunk_program(self, cbucket: int):
        """The jitted program of one bucket that prefills a chunk of a long
        prompt, or a prompt's suffix behind a cached prefix."""
        return self._jit_cached(self._programs.chunk_fn(cbucket),
                                f"{self._chunk_tag}{cbucket}",
                                donate_argnums=(4, 5))

    def _run_chunk(self, slot_id: int, padded, n: int, prefix_len: int):
        """Prefill ``n`` prompt tokens (``padded`` to a bucket) behind the
        ``prefix_len`` rows the slot already holds; returns the logits at
        the last of them."""
        pages = (jnp.asarray(self._tables_host[slot_id]) if self.paged
                 else None)
        logits, *self._state = self._run_program(
            self._prefill_jit, ("chunk", len(padded)),
            functools.partial(self._chunk_program, len(padded)),
            self.params, jnp.asarray(padded), jnp.int32(n),
            jnp.int32(prefix_len), *self._state,
            # the slot as a device scalar here and as a Python int in
            # _prefill: how these programs have always been lowered (a
            # weak-typed index lowers with a convert of its own), so a
            # peer's compile-cache entries still hit
            self._programs.slot_target(jnp.int32(slot_id), pages))
        return logits

    def _prefill(self, slot_id: int, req: Request) -> None:
        # keep the newest prompt tokens so generation fits the cache
        self._mark_admitted(req)
        tokens = self._prompt_tokens(req.tokens, req.max_new_tokens)
        n = len(tokens)
        prefix_len, block_keys = (self._slot_prefix[slot_id]
                                  if self.prefix_cache else (0, []))
        if prefix_len > 0:
            # suffix-only prefill over the reused prefix KV
            sbucket = self._bucket(n - prefix_len)
            padded = np.zeros((sbucket,), np.int32)
            padded[:n - prefix_len] = tokens[prefix_len:prefix_len + sbucket]
            logits = self._run_chunk(slot_id, padded, n - prefix_len,
                                     prefix_len)
        else:
            bucket = self._bucket(n)
            padded = np.zeros((bucket,), np.int32)
            padded[:n] = tokens[:bucket]
            pages = (jnp.asarray(
                self._slot_blocks[slot_id][:bucket // self._block_size],
                jnp.int32) if self.paged else None)
            logits, *self._state = self._run_program(
                self._prefill_jit, ("prefill", bucket),
                functools.partial(self._prefill_program, bucket),
                self.params, jnp.asarray(padded), jnp.int32(n),
                *self._state, self._programs.slot_target(slot_id, pages))
        if self.prefix_cache:
            # publish this prompt's full blocks for future prefix reuse
            # (no-ops for the ones that were themselves reused)
            blocks = self._slot_blocks[slot_id]
            for i, bkey in enumerate(block_keys):
                if (i + 1) * self._block_size <= n and i < len(blocks):
                    self._alloc.register(bkey, blocks[i])
        if self.telemetry is not None:
            # occupancy over the bucket the executed program was padded to
            # (prefix reuse prefills only the suffix)
            ran = self._bucket(n - prefix_len)
            self.telemetry.record_prefill(n - prefix_len, ran)
            self._programs.record_prompt_program(self.telemetry, ran)
        self._activate(slot_id, req, n, logits)

    def _activate(self, slot_id: int, req: Request, n: int, logits,
                  first: Optional[int] = None) -> None:
        """Claim a slot whose prompt's program (``n`` tokens) has been
        sent, and queue what opens it for decode windows.  Nothing here
        waits for the device or writes its slot state: the first token is
        sampled from ``logits`` into ``_first_tokens`` by a program of its
        own (``first`` instead where the PD wire brought the token and no
        logits), the slot's length, activity and last token go out with the
        next :meth:`_flush_slot_updates`, and the request learns its token
        in :meth:`_hand_over_first_tokens`.  The slot is claimed HERE so
        that ``run_forever``'s crash handler fails the request and frees
        its blocks when a device error surfaces at that later pull."""
        self._slots[slot_id] = req
        self._slots_gen += 1
        self._host_lengths[slot_id] = n
        if first is None:
            self._sample_first(logits, req, slot_id)
            self._slot_updates[slot_id] = [n, _ACTIVE | _TOKEN, 0]
        else:
            self._slot_updates[slot_id] = [
                n, _ACTIVE | _TOKEN | _TOKEN_FROM_HOST, first]
        self._first_pending.append((slot_id, req, first))

    def _flush_slot_updates(self) -> None:
        """Write the slots' pending device state (``_lengths``, ``_active``,
        ``_last_token``) in ONE program of fixed shapes, whatever the
        number of slots activated and released since the last flush: the
        net state of each (a slot activated and released in between ends
        inactive with its first token as last token, as two updates in a
        row left it).  Called before a decode window is dispatched and at
        the end of an admission pass and of a drain: a drain behind a
        window in flight queues one program behind it."""
        updates = self._slot_updates
        if not updates:
            return
        b = self.batch_size
        # rows: slot ids (``b``, out of range, where there is none: the
        # program drops those writes), lengths, flags, tokens the host knew
        host = np.zeros((4, b), np.int32)
        host[0] = b
        host[0, :len(updates)] = list(updates)
        host[1:, :len(updates)] = np.transpose(list(updates.values()))
        if self.telemetry is not None:
            self.telemetry.record_slot_update(len(updates))
        updates.clear()
        self._lengths, self._last_token, self._active = self._run_program(
            self._prefill_jit, "slot_update", self._slot_update_program,
            self._lengths, self._last_token, self._active,
            self._first_tokens, jnp.asarray(host))

    def _slot_update_program(self):
        """The jitted program that writes a flush's slot updates (see
        :meth:`_flush_slot_updates` for the rows of ``update``)."""
        b = self.batch_size

        def fn(lengths, last_token, active, first_tokens, update):
            slots, new_lengths, flags, tokens = update
            lengths = lengths.at[slots].set(new_lengths, mode="drop")
            active = active.at[slots].set((flags & _ACTIVE) > 0, mode="drop")
            tokens = jnp.where((flags & _TOKEN_FROM_HOST) > 0, tokens,
                               first_tokens[jnp.minimum(slots, b - 1)])
            last_token = last_token.at[
                jnp.where((flags & _TOKEN) > 0, slots, b)].set(
                    tokens, mode="drop")
            return lengths, last_token, active

        return self._jit_cached(fn, "slot_update", donate_argnums=(0, 1, 2))

    def _hand_over_first_tokens(self) -> None:
        """End of an admission pass (or of a step's completed chunked
        prompts): flush the slot updates, then bring every pending first
        token over in ONE device->host transfer, the pass's only wait for
        the device (the ``engine.first_token`` phase: it lasts until the
        last prompt's program has run), and emit them in admission order:
        first-token stamps, ``on_token``, a request that ends at once."""
        pending, self._first_pending = self._first_pending, []
        if not pending:
            return
        self._flush_slot_updates()
        with self._phase("first_token", requests=len(pending)):
            tokens = np.asarray(self._first_tokens)
        for slot_id, req, first in pending:
            self._emit(slot_id, req,
                       int(tokens[slot_id]) if first is None else first)

    def prefill_export(self, tokens: List[int],
                       max_new_tokens: int = 128) -> dict:
        """PD disaggregation, prefill side: compute the prompt's KV and the
        last-position logits WITHOUT occupying a slot; the result ships to
        a decode replica (serving/server.py serializes it).  The prompt
        budget mirrors _prefill's (max_len - max_new_tokens - 1) so the
        disaggregated path truncates exactly like a colocated one.  A family
        whose state the wire does not carry refuses in ``export_fn``.

        Parity role: the prefill worker half of the reference's SGLang PD
        integration — on TPU the KV rides the router instead of a
        bootstrap-port side channel.
        """
        max_new_tokens = max(min(max_new_tokens, self.max_len - 2), 1)
        toks = self._prompt_tokens(tokens, max_new_tokens)
        n = len(toks)
        bucket = self._bucket(n)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = toks[:bucket]
        logits, ks, vs = self._run_program(
            self._prefill_jit, ("export", bucket),
            lambda: self._jit_cached(self._programs.export_fn(bucket),
                                     f"prefill_export_b{bucket}"),
            self.params, jnp.asarray(padded), jnp.int32(n))
        logits_np = np.asarray(logits)
        return {
            "ks": np.asarray(ks[:, :n]),
            "vs": np.asarray(vs[:, :n]),
            # logits let the DECODE side sample the first token with the
            # request's temperature/top_p; first_token is the greedy
            # fallback for wire formats that drop logits
            "logits": logits_np,
            "first_token": int(np.argmax(logits_np)),
            "length": n,
        }

    def _insert_prefilled(self, slot_id: int, req: Request) -> None:
        """PD disaggregation, decode side: install a prefill replica's KV
        into a slot and start decoding from its first token."""
        self._mark_admitted(req)
        p = req.prefill
        # a prefill replica configured with a larger max_len must not be
        # able to crash this engine: the newest rows that fit are kept
        n = min(int(p["length"]), self.max_len - 2)
        pages = (jnp.asarray(
            self._slot_blocks[slot_id][:-(-n // self._block_size)],
            jnp.int32) if self.paged else None)
        self._state = self._programs.insert_rows(
            *self._state, p, n, self._programs.slot_target(slot_id, pages))
        if p.get("logits") is not None:
            # request-aware first token (temperature/top_p/top_k honored;
            # PD-wire logits arrive as numpy)
            self._activate(slot_id, req, n, p["logits"])
        else:
            self._activate(slot_id, req, n, None,
                           first=int(p["first_token"]))

    @jax.named_scope("sample")
    def _sample_on_device(self, logits, temps, top_ps, top_ks, rng):
        """Temperature/top-k/nucleus (top-p) sampling entirely on device.

        A top-k prefilter (k = min(1024, V)) bounds the sort: nucleus mass
        beyond the top 1024 logits is negligible at any usable temperature,
        and it keeps the per-step cost O(B·k) instead of O(B·V·log V).
        Per-request ``top_ks`` (0 = off) masks within the already-sorted
        prefilter, so user top-k costs one compare.  Greedy at temp<=0;
        [B] token ids cross the wire, never [B, V] logits.
        """
        b = logits.shape[0]
        k = min(1024, self.cfg.vocab_size)
        vals, idx = jax.lax.top_k(logits, k)  # [B, k] descending
        temps_c = jnp.maximum(temps, 1e-6)[:, None]
        scaled = vals / temps_c
        # user top-k rides the sorted prefilter: column j holds the
        # (j+1)-th largest logit, so keep j < top_k (clamped to the
        # prefilter width; 0 disables)
        rank = jnp.arange(k)[None, :]
        scaled = jnp.where((top_ks[:, None] <= 0) | (rank < top_ks[:, None]),
                           scaled, -jnp.inf)
        probs = jax.nn.softmax(scaled, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # nucleus: smallest prefix whose mass reaches top_p (the first token
        # is always kept — its prefix-exclusive mass is 0)
        keep = (cum - probs) < top_ps[:, None]
        masked = jnp.where(keep, scaled, -jnp.inf)
        gumbel = -jnp.log(-jnp.log(
            jax.random.uniform(rng, (b, k), minval=1e-20, maxval=1.0)
        ) + 1e-20)
        choice = jnp.argmax(masked + gumbel, axis=-1)
        sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
        greedy = idx[:, 0]
        return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)

    #: decode-window sizes; each compiles once.  The biggest window is the
    #: steady-state path; the small ones avoid large
    #: overshoot on short tails.  Trade-off: streaming callbacks burst up
    #: to 64 tokens and a queued prompt waits up to one window for a slot —
    #: latency-sensitive deployments can override this class attribute.
    #: (The first tokens of one admission pass arrive together too, at the
    #: pass's end: it waits for the device once, not once a request.)
    DECODE_WINDOWS = (8, 32, 64)

    #: fixed per-window dispatch overhead expressed in decode steps (host
    #: round-trip + emit loop); _pick_window weighs overshoot against this
    #: when splitting tails
    WINDOW_DISPATCH_COST_STEPS = 8

    def _pick_window(self, remaining: int) -> int:
        """Window size minimizing total tail cost = wasted device steps +
        per-window dispatch overhead (WINDOW_DISPATCH_COST_STEPS each).

        Steady state (remaining >= the largest window): largest window.
        Tails weigh both terms — remaining=33 runs 32 then 8 (7 wasted +
        one extra dispatch beats 31 wasted in one 64), but remaining=20
        covers with one 32 (12 wasted beats three 8-windows' dispatches).
        Handles any DECODE_WINDOWS override order (sorted internally)."""
        ws = sorted(self.DECODE_WINDOWS)
        if remaining >= ws[-1]:
            return ws[-1]
        f = self.WINDOW_DISPATCH_COST_STEPS

        def cost(r: int) -> int:
            if r <= 0:
                return 0
            return min((f + w - r) if w >= r else (f + cost(r - w))
                       for w in ws)

        best_w, best_c = ws[-1], None
        for w in ws:
            c = (f + w - remaining) if w >= remaining \
                else (f + cost(remaining - w))
            # ties break toward the LARGER window (same total cost, but
            # more of the tail lands in the first dispatch)
            if best_c is None or c < best_c or (c == best_c and w > best_w):
                best_w, best_c = w, c
        return best_w

    def _inflight_steps(self) -> int:
        """Steps of the window still in flight: what the host's lengths
        lag the device's by."""
        return self._pending["window"] if self._pending is not None else 0

    def _ragged_blocks(self, window: int) -> int:
        """Block-table columns the NEXT decode window can touch, rounded
        up to a power of two (bounds the jit-key cardinality at
        log2(blocks_per_slot) programs per window size).

        Host lengths lag the device by the in-flight window during
        pipelining, so its width is added back before sizing; slots
        admitted (or chunk-finished) since that window dispatched weren't
        in its decoding set, so counting the in-flight width for them too
        only over-sizes the bucket — never under."""
        if not self._ragged:
            return self._blocks_per_slot
        inflight = self._inflight_steps()
        need = 0
        for slot_id, req in enumerate(self._slots):
            if req is None or slot_id in self._chunking:
                continue
            need = max(need,
                       int(self._host_lengths[slot_id]) + inflight + window)
        need = min(need, self.max_len)
        nbk = max(-(-need // self._block_size), 1)
        bucket = 1
        while bucket < nbk:
            bucket *= 2
        return min(bucket, self._blocks_per_slot)

    def _dispatch_window(self, remaining: int):
        """Dispatch one decode window asynchronously; returns the pending
        record ({tokens handle, window, remaining_after}) or None.

        ``remaining`` is the caller's view of the most tokens any active
        request still needs — passed in rather than recomputed because with
        a window in flight ``req.output`` lags the device by one window."""
        if remaining <= 0 or not any(
                req is not None and slot_id not in self._chunking
                for slot_id, req in enumerate(self._slots)):
            return None
        window = self._pick_window(remaining)
        sampling = any(
            req is not None and req.temperature > 0.0 for req in self._slots)
        self._window_seq += 1
        with self._phase("dispatch_window", window=self._window_seq):
            return self._dispatch_window_program(remaining, window, sampling)

    def _decode_window_program(self, window: int, sampling: bool,
                               nbk: Optional[int]):
        """The jitted decode window for one (window, sampling,
        table-bucket) key."""
        return self._jit_cached(
            self._programs.decode_window_fn(window, sampling, nbk),
            f"decode_w{window}_s{int(sampling)}"
            + (f"_kb{nbk}" if nbk is not None else ""),
            donate_argnums=(4, 5))

    def _dispatch_window_program(self, remaining: int, window: int,
                                 sampling: bool):
        """Build the window's tables and per-slot constants, enqueue the
        program."""
        nbk = self._ragged_blocks(window) if self.paged else None
        # the window reads the slots' state as the host last left it
        self._flush_slot_updates()

        # Host->device transfers per WINDOW must be near zero, so
        # everything below is cached against the current slot assignment
        # (an admission or release bumps _slots_gen; table buckets cache
        # per ragged width) and rng only advances when sampling (greedy
        # windows ignore it — reuse one constant key).
        gen = self._slots_gen
        if self._decode_consts is None or self._decode_consts[0] != gen:
            temps = jnp.asarray([
                (req.temperature if req is not None else 0.0)
                for req in self._slots
            ], jnp.float32)
            top_ps = jnp.asarray([
                (req.top_p if req is not None else 1.0)
                for req in self._slots
            ], jnp.float32)
            top_ks = jnp.asarray([
                (req.top_k if req is not None else 0)
                for req in self._slots
            ], jnp.int32)
            self._decode_consts = (gen, temps, top_ps, top_ks, {})
        _, temps, top_ps, top_ks, tables_by_bucket = self._decode_consts
        if nbk not in tables_by_bucket:
            tables_by_bucket[nbk] = (
                jnp.asarray(self._tables_host[:, :nbk]) if self.paged
                else jnp.zeros((self.batch_size, 1), jnp.int32))
        tables = tables_by_bucket[nbk]
        if sampling:
            self._rng_key, sub = jax.random.split(self._rng_key)
        else:
            sub = self._rng_key
        # behind its two state trees a window may return what it counted
        # about itself (a model with experts its load, a looped one its
        # passes): the provider's to record, where the window is drained
        tokens_all, self._last_token, self._lengths, *rest = \
            self._run_program(
                self._decode_jit, (window, sampling, nbk),
                functools.partial(self._decode_window_program, window,
                                  sampling, nbk),
                self.params, self._last_token, self._lengths, self._active,
                *self._state, temps, top_ps, top_ks, tables, sub,
            )
        self._state, window_counts = rest[:2], rest[2:]
        # snapshot which slots this window actually decodes for: by drain
        # time a mid-chunking slot may have finished its prefill (left
        # _chunking), but ITS rows in this window are still junk
        decoding = frozenset(
            slot_id for slot_id, req in enumerate(self._slots)
            if req is not None and slot_id not in self._chunking)
        pending = {"tokens": tokens_all, "window": window,
                   "seq": self._window_seq,
                   "remaining_after": remaining - window,
                   "decoding": decoding, "window_counts": window_counts}
        if self.telemetry is not None:
            self._record_dispatch(decoding, pending)
        return pending

    def _kv_used_fraction(self) -> float:
        """KV capacity in use: allocated blocks over the usable pool
        (paged; parked-but-evictable prefix blocks count as used — they
        hold live KV) or cached rows over batch * max_len (dense)."""
        if self.paged:
            usable = self._alloc.num_blocks - 1  # block 0 is the NULL block
            return (usable - self._alloc.free_blocks) / max(usable, 1)
        return (float(self._host_lengths.sum())
                / max(self.batch_size * self.max_len, 1))

    def _record_dispatch(self, decoding, pending: dict) -> None:
        """Per-window telemetry at dispatch time (batch occupancy, KV
        utilization, queue depth) + the monotonic stamp the drain uses
        for inter-token latency.  Only called when telemetry is on."""
        t = self.telemetry
        if t is None:  # callers gate too; cheap belt for new call sites
            return
        t.record_window(len(decoding), self.batch_size)
        t.record_kv_utilization(self._kv_used_fraction())
        t.record_queue_depth(self._queue.qsize())
        t.record_prefill_backlog(self._chunk_backlog())
        pending["t0"] = time.perf_counter()

    def _chunk_backlog(self) -> int:
        """Prompt tokens not yet dispatched across mid-chunking slots —
        the chunked-prefill backlog a load-aware router steers around."""
        return sum(
            max(len(st["tokens"]) - st["done"], 0)
            for st in self._chunking.values() if "logits" not in st)

    def _drain_window(self) -> None:
        """Pull the in-flight window's tokens (and what it counted about
        itself) to the host and emit them — the ONE device->host sync per
        window.  The slots the emit released go out as one program at its
        end (:meth:`_flush_slot_updates`): behind a window dispatched
        ahead that is one program queued, and the thread does not wait."""
        p = self._pending
        if p is None:
            return
        self._pending = None
        window_counts = None
        with self._phase("pull", window=p["seq"]):
            tokens_np = np.asarray(p["tokens"])
            if self.telemetry is not None and p.get("window_counts"):
                window_counts = np.asarray(p["window_counts"][0])
        emitted = 0
        with self._phase("emit", window=p["seq"]):
            for step in range(p["window"]):
                for slot_id, req in enumerate(self._slots):
                    if req is None or slot_id not in p["decoding"]:
                        # finished mid-window (discard overshoot) or was
                        # still prefilling at DISPATCH time (this window
                        # carried junk for the slot even if its prefill
                        # has since finished)
                        continue
                    self._host_lengths[slot_id] += 1  # mirrors device
                    emitted += 1
                    self._emit(slot_id, req, int(tokens_np[step, slot_id]))
            self._flush_slot_updates()
        if self.telemetry is not None and "t0" in p:
            self.telemetry.record_drain(
                emitted, time.perf_counter() - p["t0"], len(p["decoding"]),
                steps=p["window"], batch_size=self.batch_size)
            if window_counts is not None:
                self._programs.record_window_counts(self.telemetry,
                                                    window_counts)

    def _sample_first(self, logits, req: Request, slot_id: int) -> None:
        """Sample a request's FIRST token with the same fused on-device
        sampler the decode windows use (:meth:`_sample_on_device`) into
        ``_first_tokens[slot_id]``.  The call is asynchronous: the token
        stays on the device until the pass that sent the prompt ends, and
        one pull brings every first token of the pass over
        (:meth:`_hand_over_first_tokens`).

        No logits-sized transfer and no wait a request: a pull of each
        token as it was sampled left the device dry between one prompt's
        program and the next.  Greedy (temp<=0) is argmax, so greedy first
        tokens are bit-identical to a full forward's; sampled ones are
        seed-deterministic through the engine's threaded ``jax.random``
        key, split here on the host in admission order."""
        if req.temperature > 0.0:
            self._rng_key, sub = jax.random.split(self._rng_key)
        else:
            sub = self._rng_key  # greedy ignores it; don't burn entropy
        # the request's constants and its slot as ONE host array: one
        # transfer, no conversion program a scalar.  Both integers are
        # exact in float32 (a top-k past the sampler's 1,024-wide prefilter
        # keeps every rank, as any larger one does)
        consts = jnp.asarray(np.array(
            [req.temperature, req.top_p, min(req.top_k or 0, 1024), slot_id],
            np.float32))
        self._first_tokens = self._run_program(
            self._prefill_jit, "first_token", self._first_token_program,
            jnp.asarray(logits), consts, sub, self._first_tokens)

    def _first_token_program(self):
        """The jitted sampler of one request's first token: writes it into
        the donated ``[batch_size]`` vector at the slot."""
        def fn(logits, consts, rng, first_tokens):
            temp, top_p, top_k, slot = consts
            token = self._sample_on_device(
                logits[None, :], temp[None], top_p[None],
                top_k.astype(jnp.int32)[None], rng)[0]
            return first_tokens.at[slot.astype(jnp.int32)].set(token)

        return self._jit_cached(fn, "first_token_sample",
                                donate_argnums=(3,))

    def _emit(self, slot_id: int, req: Request, token: int) -> None:
        if (not req.cancelled and req.deadline is not None
                and time.time() > req.deadline):
            # deadline passed mid-decode: stop generating, free the slot
            # (and, below via _release, the KV blocks) for live requests
            req.cancel(reason="deadline")
        if req.cancelled:
            # cancelled mid-generation (stop sequence, client disconnect):
            # discard this token and free the slot for the queue
            req.finish_reason = req.finish_reason or "cancelled"
            req.finished_at = req.now()
            self._release(slot_id)
            req.done.set()
            if self.telemetry is not None:
                self.telemetry.record_finished(req)
            return
        if req.first_token_at is None:
            req.first_token_at = req.now()
            if self.telemetry is not None:
                # once per request, never on the per-token path
                self.telemetry.record_first_token(
                    req.first_token_at - req.submitted_at,
                    trace_id=req.trace_id)
        req.output.append(token)
        if req.on_token is not None:
            req.on_token(token)
        hit_eos = req.eos_id is not None and token == req.eos_id
        length = int(self._host_lengths[slot_id]) + 1  # +1 pending for this token
        out_of_room = length >= self.max_len - 1
        if len(req.output) >= req.max_new_tokens or hit_eos or out_of_room:
            # a stop-sequence cancel on this very token already set a
            # reason — don't overwrite it with "length"
            req.finish_reason = req.finish_reason or (
                "stop" if hit_eos else "length")
            req.finished_at = req.now()
            self._release(slot_id)
            req.done.set()
            if self.telemetry is not None:
                self.telemetry.record_finished(req)

    def _release(self, slot_id: int) -> None:
        """Free a slot; its device state (inactive, no length) goes out
        with the next :meth:`_flush_slot_updates`.  A slot released before
        its activation was flushed keeps that update's last token."""
        self._release_host(slot_id)
        update = self._slot_updates.setdefault(slot_id, [0, 0, 0])
        update[0] = 0
        update[1] &= ~_ACTIVE

    def _release_host(self, slot_id: int) -> None:
        """Host-side half of release: safe to call when the device runtime
        is wedged (run_forever's crash handler)."""
        self._slots[slot_id] = None
        self._slots_gen += 1
        self._host_lengths[slot_id] = 0
        if self.paged and self._slot_blocks[slot_id]:
            # refcounted in prefix-cache mode (shared blocks park in the
            # allocator's LRU); plain free otherwise
            self._alloc.release(self._slot_blocks[slot_id])
            self._slot_blocks[slot_id] = []
            self._slot_prefix[slot_id] = (0, [])
            self._tables_host[slot_id, :] = 0
