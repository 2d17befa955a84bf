"""Continuous-batching inference engine (JetStream-style) on the Llama stack.

The serving counterpart of models/llama.py: a fixed pool of decode *slots*
shares one batched KV cache; prefill computes a prompt's K/V with the full
forward pass and inserts them into a free slot; decode advances ALL active
slots one token per step with per-slot positions. Static shapes throughout
(prompt lengths padded to buckets) so both phases jit-compile once and stay
on the MXU.

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
from dstack_tpu.models.ling_hybrid import LingHybridConfig
from dstack_tpu.models.ling_hybrid import init_params as hybrid_init
from dstack_tpu.models.llama import (
    LlamaConfig,
    Params,
    init_params,
    output_head,
)
from dstack_tpu.ops.pool import scatter_rows as _scatter_rows
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.ops.rotary import apply_rope, rope_frequencies
from dstack_tpu.serving.hybrid import HybridPrograms
from dstack_tpu.serving.paging import BlockAllocator, PrefixBlockAllocator
from dstack_tpu.serving.quant import (
    dequantize_kv,
    dequantize_kv4,
    qmatmul,
    quantize_kv,
    quantize_kv4,
    quantize_params,
)

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)

logger = logging.getLogger(__name__)


def _paged_kernel_default() -> bool:
    """Whether paged decode attention should run the Pallas block-table
    kernel (ops/flash_attention.py paged_decode_attention) instead of the
    XLA gather path.  ``DSTACK_TPU_PAGED_ATTN_KERNEL``: "auto" (default —
    on for a real TPU backend, off for CPU/interpret where the XLA path
    wins), "1"/"0" to force.  Whichever is chosen is the only path: a
    kernel the compiler refuses fails the decode, nothing falls back."""
    v = os.environ.get("DSTACK_TPU_PAGED_ATTN_KERNEL", "auto")
    if v == "auto":
        return jax.default_backend() == "tpu"
    return v not in ("0", "false", "off")


def _named_jit(fn, name: str, **jit_kwargs):
    """``jax.jit`` of ``fn`` under ``name``: the compiled program shows as
    ``jit_<name>`` on a profiler trace's ``XLA Modules`` line and in HLO
    dumps (a ``functools.partial`` or a local ``fn`` would read
    ``jit__unknown`` / ``jit_fn``).  The only ``jax.jit`` in this module."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kwargs)


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


# Device-side regions carry a jax.named_scope so that a profiler trace and an
# HLO dump say which part of a program an operation belongs to: qkv, attn,
# paged_attn, mlp, lm_head, sample, kv_insert (prefill's write of a prompt's
# K/V), kv_window_write (the decode window's one write at its end).


@jax.named_scope("mlp")
def _mlp_block(h, lp, cfg: LlamaConfig, token_mask=None):
    """Dense SwiGLU or routed-expert MLP on [B, S, D] normed hiddens.

    The rest of the serving math (attention, KV cache, sampling) is
    model-agnostic, so this one dispatch point is what makes the engine
    serve both Llama-family and Mixtral-style MoE checkpoints.  MoE decode
    routes each generated token independently through the same GShard
    static-capacity path training uses (models/moe.py).
    """
    if "router" not in lp:
        gated = jax.nn.silu(qmatmul(h, lp["w_gate"], cfg.dtype))
        up = qmatmul(h, lp["w_up"], cfg.dtype)
        return qmatmul(gated * up, lp["w_down"], cfg.dtype)
    from dstack_tpu.models.moe import _moe_mlp

    b, s, _ = h.shape
    # Decode (one token per slot): force DROPLESS capacity — an expert can
    # hold every token, so no generated token ever loses an expert to
    # capacity pressure from its batch neighbours (GShard capacity is a
    # training-time economy; at t=B the dispatch tensor is tiny anyway).
    # Prefill: `token_mask` keeps bucket-padding out of routing (pads must
    # not steal real tokens' expert slots), and capacity derives from the
    # bucket length, which is >= the unpadded training forward's — so a
    # served prompt can only ever KEEP tokens training-time capacity would
    # drop, never lose ones it would keep.
    capacity = b * s if s == 1 else None
    out, _aux = _moe_mlp(h, lp, cfg, None, None, capacity=capacity,
                         token_mask=token_mask)
    return out


def _layer_kv(params, cfg: LlamaConfig, x, positions, inv_freqs,
              token_mask=None):
    """Per-layer K/V for a full sequence — shared by prefill.
    ``token_mask`` [B, S] marks real (non-padding) tokens for MoE routing."""
    b, s, _ = x.shape

    def layer(carry, lp):
        x = carry
        q, k, v = _decode_qkv(x, lp, cfg, positions, inv_freqs, b, s)
        attn = _masked_attention(q, k, v, positions, positions)
        x = x + qmatmul(attn.reshape(b, s, cfg.q_dim),
                       lp["wo"], cfg.dtype)
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp_block(h, lp, cfg, token_mask)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    return x, ks, vs  # ks/vs: [L, B, S, Hkv, D]


def _last_logits(params, cfg: LlamaConfig, x, length):
    """Logits at the last of ``length`` real positions of a [1, S, D]
    prefill activation."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        head = output_head(params, cfg)
        return qmatmul(x[0, length - 1, :], head, cfg.dtype,
                       preferred=jnp.float32)


def _prompt_forward(params, cfg: LlamaConfig, padded, length, bucket: int):
    """Forward over a padded prompt: (last-position logits, ks, vs).
    The single source of truth for prefill math — used by both the
    slot-inserting prefill jit and the PD export jit."""
    positions = jnp.arange(bucket)[None, :]
    inv_freqs = jnp.asarray(rope_frequencies(
        cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    x = params["embed"].astype(cfg.dtype)[padded][None, :, :]
    token_mask = (jnp.arange(bucket)[None, :] < length)
    x, ks, vs = _layer_kv(params, cfg, x, positions, inv_freqs, token_mask)
    return _last_logits(params, cfg, x, length), ks, vs


@jax.named_scope("qkv")
def _decode_qkv(x, lp, cfg: LlamaConfig, positions, inv_freqs, b: int,
                m: int = 1):
    """Per-token projections + RoPE — factored out so the dense and paged
    branches of the buffered decode (and the prefill programs) can never
    diverge numerically.  ``m`` is the tokens per row: 1 for plain decode,
    draft_k+1 for speculative verification, the bucket for a prefill."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = qmatmul(h, lp["wq"], cfg.dtype).reshape(
        b, m, cfg.num_heads, cfg.head_dim)
    k = qmatmul(h, lp["wk"], cfg.dtype).reshape(
        b, m, cfg.num_kv_heads, cfg.head_dim)
    v = qmatmul(h, lp["wv"], cfg.dtype).reshape(
        b, m, cfg.num_kv_heads, cfg.head_dim)
    return (apply_rope(q, positions, inv_freqs),
            apply_rope(k, positions, inv_freqs), v)


def _decode_layer_tail(x, attn, lp, cfg: LlamaConfig, b: int, m: int = 1):
    """Shared post-attention half of a decode layer (wo + MLP)."""
    x = x + qmatmul(attn.reshape(b, m, cfg.q_dim), lp["wo"], cfg.dtype)
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    return x + _mlp_block(h, lp, cfg)


def _kv_mat(cache_leaf, dtype):
    """A KV tensor ready for attention: plain arrays pass through;
    quantized dicts dequantize — int8 {"q","s"} or nibble-packed int4
    {"q4","s"} (the dict key IS the format marker).  XLA fuses the
    convert+scale into the consuming dot, so the quantized bytes are what
    cross HBM."""
    if isinstance(cache_leaf, dict):
        if "q4" in cache_leaf:
            return dequantize_kv4(cache_leaf["q4"], cache_leaf["s"], dtype)
        return dequantize_kv(cache_leaf["q"], cache_leaf["s"], dtype)
    return cache_leaf


def _kv_pack(rows, bits: int = 8):
    """Quantize bf16 K/V rows [..., D] into the cache's dict form:
    {"q","s"} at 8 bits, {"q4","s"} nibble-packed at 4."""
    if bits == 4:
        q4, s = quantize_kv4(rows)
        return {"q4": q4, "s": s}
    q, s = quantize_kv(rows)
    return {"q": q, "s": s}


def _kv_map(cache, rows, fn, lanes: bool = False):
    """Apply ``fn(cache_leaf, rows_leaf)`` over a cache that is either a
    plain array or a quantized {"q"|"q4","s"} dict (rows packed to
    match).  ``fn`` must be shape-generic over trailing dims: the int4
    "q4" leaf carries D/2 packed bytes and "s" no D dim at all.
    ``lanes``: the cache is the PAGED pool, whose leaves fold the kv heads
    into the lane dim (:func:`_fold_heads`; "s" keeps [..., Hkv]) — the
    new rows are folded to match, never the pool."""
    fold = _fold_heads if lanes else (lambda a: a)
    if isinstance(cache, dict):
        qk = "q4" if "q4" in cache else "q"
        packed = _kv_pack(rows, bits=4 if qk == "q4" else 8)
        return {qk: fn(cache[qk], fold(packed[qk])),
                "s": fn(cache["s"], packed["s"])}
    return fn(cache, fold(rows))


def _fold_heads(a):
    """[..., Hkv, D] K/V rows -> [..., Hkv*D], lane = h*D + d: the paged
    pool's stored form, which is the decode kernel's operand."""
    return a.reshape(a.shape[:-2] + (-1,))


def _split_heads(view, hkv: int):
    """Inverse of :func:`_fold_heads` for a view GATHERED from the paged
    pool (array or quantized dict; "s" is [..., Hkv] already)."""
    split = lambda a: a.reshape(a.shape[:-1] + (hkv, -1))
    if isinstance(view, dict):
        return {key: (leaf if key == "s" else split(leaf))
                for key, leaf in view.items()}
    return split(view)


@jax.named_scope("kv_window_write")
def _dense_window_insert(cache, win, widx, in_window):
    """End-of-window bulk insert for the DENSE cache: cache position (b, s)
    takes window column ``widx[b, s]`` wherever ``in_window[b, s]`` — the
    one write the buffered formulations (plain and speculative) amortize
    the whole window's cache updates into."""
    def one(leaf, rows):
        rows_t = jnp.moveaxis(rows, 1, 2)            # [L, B, cols, ...]
        idx = widx[None, :, :]
        idx = idx.reshape(idx.shape + (1,) * (rows_t.ndim - 3))
        picked = jnp.take_along_axis(rows_t, idx, axis=2)
        sel = in_window[None, :, :]
        sel = sel.reshape(sel.shape + (1,) * (rows_t.ndim - 3))
        return jnp.where(sel, picked, leaf)

    return _kv_map(cache, win, one)


def _suffix_layer(x, lp, cfg: LlamaConfig, positions, inv_freqs, kv_pos,
                  token_mask, layer_k, layer_v, insert, gather,
                  lanes: bool = False):
    """One transformer layer of a suffix/chunk prefill: project the new
    tokens' K/V, ``insert`` them into the slot's cache, then attend the
    new queries over the ``gather``-ed full slot span (earlier rows +
    causal within the new ones, absolute RoPE positions).  The insert and
    gather callbacks are the ONLY difference between the paged suffix
    prefill (block scatter/gather) and the dense chunked prefill (row
    slice) — both share this body.  The paged one (``lanes``) hands the
    WHOLE pool through as ``layer_k``/``layer_v``: its callbacks address
    the layer in place."""
    sbucket = x.shape[1]
    q, k, v = _decode_qkv(x, lp, cfg, positions, inv_freqs, 1, sbucket)
    with jax.named_scope("kv_insert"):
        layer_k = _kv_map(layer_k, k, insert, lanes)
        layer_v = _kv_map(layer_v, v, insert, lanes)
    kv_k = _kv_mat(gather(layer_k), cfg.dtype)
    kv_v = _kv_mat(gather(layer_v), cfg.dtype)
    attn = _masked_attention(q, kv_k, kv_v, positions, kv_pos)
    x = x + qmatmul(attn.reshape(1, sbucket, cfg.q_dim), lp["wo"], cfg.dtype)
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    x = x + _mlp_block(h, lp, cfg, token_mask)
    return x, layer_k, layer_v


@jax.named_scope("attn")
def _masked_attention(q, k, v, q_pos, kv_pos):
    """Causal GQA attention with explicit position masks (prefill)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, s, hkv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / (d ** 0.5)
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None])[:, None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, s, hq, d)


class InferenceEngine:
    """Slot-based continuous batching over one model replica.

    batch_size slots share a [L, B, max_len, Hkv, D] cache; `step()` is one
    scheduling iteration: admit waiting prompts into free slots (prefill),
    then advance every active slot a WINDOW of tokens in one dispatch
    (`_decode_window_fn_buffered`) with on-device nucleus sampling.  Streaming
    callbacks therefore arrive in bursts of up to `DECODE_WINDOWS[-1]`
    tokens, and a queued prompt waits at most one window for a free slot —
    the price of amortizing the host round-trip across the window.
    """

    #: Speculation x chunked-prefill overlap sweep winner (bench.py
    #: run_decode_overlap_sweep, PR 18): k=2 beat every larger draft at
    #: every chunk size — past 2, the widened verify forward costs more
    #: than the extra accepted tokens return on the mixed workload — and
    #: chunk=512 held background decode within range of smaller chunks at
    #: the best arrival TTFT.  speculation_k=None resolves to the tuned
    #: value; tests/compute/test_serving_decode.py pins both so a default
    #: change is a deliberate re-sweep, not drift.
    TUNED_SPECULATION_K = 2
    TUNED_PREFILL_CHUNK = 512

    def __init__(
        self,
        cfg: LlamaConfig,
        params: Optional[Params] = None,
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
        speculation: Optional[str] = None,
        speculation_k: Optional[int] = None,
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

        ``speculation="ngram"``: n-gram (prompt-lookup) speculative
        decoding — GREEDY windows verify ``speculation_k`` draft tokens
        per step in one widened forward, emitting several tokens per
        weight pass when generation repeats n-grams from the context
        (code, extraction, chat-with-history).  Output tokens are
        identical to non-speculative greedy; sampled requests and paged
        engines use the plain window.  See _decode_window_fn_spec.

        ``telemetry``: a `dstack_tpu.telemetry.serving.EngineTelemetry`
        recording queue-wait/TTFT/inter-token histograms, batch occupancy,
        KV utilization, preemptions and spec-decode acceptance from the
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
        milliseconds instead of paying the 11.8-17.4 s compile leg
        (an earlier v5e run, 2026-08-01).  Defaults to the env-configured cache
        (``DSTACK_COMPILE_CACHE`` / ``DSTACK_COMPILE_CACHE_PEERS``);
        both unset → no caching, the plain jit path.  Hit/miss counters
        surface on ``/load`` and ``/stats``.
        """
        self.cfg = cfg
        self.telemetry = telemetry
        #: the state and the programs of a model whose memory is not K and
        #: V rows (serving/hybrid.py: a recurrent state beside a paged
        #: latent pool); None for the Llama family, whose programs are this
        #: class's own.  The scheduler below is one for both: beside the
        #: constructor only ``_reset_device_state`` and the three builders
        #: of paged programs (prefill, chunk, decode window) ask which.
        self._hybrid = None
        #: where a paged prefill or chunk program writes, from (slot, its
        #: pages): the pages, and for per-slot recurrent state the slot too
        self._slot_target = lambda slot_id, pages: pages
        #: why prefill/decode disaggregation is refused, if it is
        self._pd_refusal: Optional[str] = None
        hybrid = isinstance(cfg, LingHybridConfig)
        if hybrid:
            self._pd_refusal = (
                "prefill/decode disaggregation is not served for this "
                "model: the wire carries K and V rows, not a recurrent "
                "state and latent rows")
            for refused, needs in (
                (not paged, "paged=False: the MLA layers' latent rows live "
                 "in the paged pool, a dense latent cache is not written"),
                (prefix_cache, "prefix_cache: a cached block would need a "
                 "snapshot of the recurrent state at its boundary"),
                (speculation, "speculation: rejected drafts would need the "
                 "recurrent state rolled back"),
                (kv_quantize, "kv_quantize: latent pages would need scales "
                 "and an absorbed product over quantized rows"),
                (quantize, "quantize: the grouped expert product would need "
                 "int8 forms of the expert stacks"),
                (mesh is not None, "a mesh: it would need the expert "
                 "exchange and sharding rules for the recurrent state"),
            ):
                if refused:
                    raise ValueError(
                        f"{type(cfg).__name__} is not served with {needs}")
        self.compile_cache = (compile_cache if compile_cache is not None
                              else CompileCache.from_env())
        self.batch_size = batch_size
        self.max_len = min(max_len, cfg.max_seq_len)
        self.paged = paged
        if kv_quantize not in (None, "int8", "int4"):
            raise ValueError(f"unsupported kv_quantize={kv_quantize!r} "
                             "(only 'int8' or 'int4')")
        if kv_quantize == "int4" and cfg.head_dim % 2:
            raise ValueError("int4 KV packing needs an even head_dim")
        self.kv_quantize = kv_quantize
        self.kv_quant = kv_quantize is not None
        #: paged decode reads only a power-of-two BUCKET of each slot's
        #: block table sized to the longest active slot (ragged lengths),
        #: instead of the full blocks_per_slot span; DSTACK_TPU_RAGGED_DECODE=0
        #: restores the full-span gather (the dense-paged bench baseline)
        self._ragged = os.environ.get(
            "DSTACK_TPU_RAGGED_DECODE", "1") != "0"
        #: Pallas block-table decode kernel (resolved once at init)
        self._paged_kernel = _paged_kernel_default()
        self.mesh = mesh
        self._policy = None
        t = 1  # tensor-parallel degree
        if mesh is not None:
            from dstack_tpu.models.llama import ShardingPolicy

            self._policy = sharding_policy or ShardingPolicy(
                batch_axes=(), fsdp_axis=None, tensor_axis="tensor")
            if (self._policy.tensor_axis
                    and self._policy.tensor_axis not in mesh.axis_names):
                raise ValueError(
                    f"mesh axes {mesh.axis_names} lack the policy's tensor "
                    f"axis {self._policy.tensor_axis!r}; name the mesh axis "
                    f"to match (or pass a sharding_policy)")
            t = (mesh.shape.get(self._policy.tensor_axis, 1)
                 if self._policy.tensor_axis else 1)
            if cfg.num_kv_heads % t or cfg.num_heads % t:
                raise ValueError(
                    f"tensor-parallel serving needs head counts divisible "
                    f"by the tensor degree: heads {cfg.num_heads}/"
                    f"{cfg.num_kv_heads}, tensor={t}")
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
            self._alloc = (PrefixBlockAllocator(n_blocks) if prefix_cache
                           else BlockAllocator(n_blocks))
            # The buffered-window decode materializes a dense-equivalent
            # [L, B, span] linear KV view per window — HBM sizing must
            # budget pool + one dense cache, so heavy pool overcommit does
            # not deliver a proportional memory saving during decode.
            dense_equiv = batch_size * self._blocks_per_slot
            if n_blocks < dense_equiv // 2:
                logger.warning(
                    "paged KV pool (%d blocks) is overcommitted well below "
                    "the dense equivalent (%d): decode still needs a "
                    "dense-equivalent linear-view allowance in HBM "
                    "(see ROOFLINE.md, serving decode)", n_blocks, dense_equiv)
            if hybrid:
                self._hybrid = HybridPrograms(
                    cfg, batch_size=batch_size, max_len=self.max_len,
                    block_size=kv_block_size, num_blocks=n_blocks,
                    sample=self._sample_on_device)
                self._slot_target = self._hybrid.slot_target
            lanes = (cfg.latent_lanes if hybrid
                     else cfg.num_kv_heads * cfg.head_dim // t)
            if self._paged_kernel and lanes % 128:
                # the pool is stored as the decode kernel's operand only
                # in whole 128-lane tiles: the TPU compiler keeps a
                # narrower or ragged pool with the blocks minor-most and
                # converts ALL of it around every program that reads it
                # (tests/compute/test_tpu_compile.py)
                logger.warning(
                    "paged KV pool rows are %d lanes a device (kv heads x "
                    "head_dim / tensor degree), not a multiple of 128: the "
                    "TPU converts the whole pool's layout around every "
                    "decode window and holds a second copy of it; use a "
                    "tensor degree that leaves whole multiples of 128",
                    lanes)
            self._tables_host = np.zeros(
                (batch_size, self._blocks_per_slot), np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(batch_size)]
        elif prefix_cache:
            raise ValueError("prefix_cache requires paged=True (the cache "
                             "is block-addressed)")
        if prefill_chunk is not None and prefill_chunk < 1:
            # 0 would make every request chunk forever on empty slices
            raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = prefill_chunk
        if speculation not in (None, "ngram"):
            raise ValueError(f"unsupported speculation={speculation!r} "
                             "(only 'ngram')")
        if speculation and paged:
            raise ValueError("speculation requires the dense cache")
        self.speculation = speculation
        self.speculation_k = (speculation_k if speculation_k is not None
                              else self.TUNED_SPECULATION_K)
        #: slot_id -> {"tokens", "done", ("logits", "n")} for prompts
        #: mid-chunked-prefill (see prefill_chunk)
        self._chunking: dict = {}
        self.prefix_cache = prefix_cache
        #: per-slot (prefix_len, block_keys) staged between reserve and
        #: prefill (prefix-cache mode)
        self._slot_prefix: List[tuple] = [(0, []) for _ in range(batch_size)]
        from dstack_tpu.models.moe import MoEConfig, init_params as moe_init

        self._is_moe = (
            isinstance(cfg, MoEConfig)
            or (params is not None and "router" in (
                params["layers"][0]
                if isinstance(params["layers"], (list, tuple))
                else params["layers"])))
        if mesh is not None and self._is_moe:
            e = mesh.shape.get("expert", 1)
            if e > 1 and cfg.num_experts % e:
                raise ValueError(
                    f"expert-parallel serving needs num_experts "
                    f"({cfg.num_experts}) divisible by the expert mesh "
                    f"degree ({e})")
        if params is None:
            if mesh is not None:
                # init directly sharded — the full model must never
                # materialize on one device (the whole point of mesh serving
                # is models that don't fit one chip's HBM)
                init = moe_init if isinstance(cfg, MoEConfig) else init_params
                shapes = jax.eval_shape(
                    lambda: init(jax.random.PRNGKey(0), cfg))
                params = _named_jit(
                    lambda: init(jax.random.PRNGKey(rng_seed), cfg),
                    "init_params",
                    out_shardings=self._param_shardings(shapes),
                )()
            else:
                params = (hybrid_init if hybrid
                          else moe_init if isinstance(cfg, MoEConfig)
                          else init_params)(jax.random.PRNGKey(rng_seed), cfg)
        elif mesh is not None:
            # host (numpy / checkpoint) arrays transfer shard-wise here;
            # already-committed device arrays get resharded
            params = jax.device_put(params, self._param_shardings(params))
        self.params = params
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(f"unsupported quantize={quantize!r} "
                                 "(only 'int8')")
            # weight-only int8 (serving/quant.py): decode is weight-read
            # bound, so int8 weights ~halve the per-step HBM floor; tied
            # models get an int8 COPY of the head so the logits matmul
            # (the single largest read) streams int8 too
            # under a mesh this runs on already-sharded arrays (executes
            # distributed); the device_put below only re-aligns the int8
            # scales and the tied-head copy
            self.params = quantize_params(
                self.params, tied_head_copy=cfg.tie_embeddings)
            if mesh is not None:
                self.params = jax.device_put(
                    self.params, self._param_shardings(self.params))
        if mesh is None:
            # commit the params: an UNcommitted tree lowers without
            # mhlo.sharding annotations while a checkpoint-restored
            # (committed) one carries "{replicated}", so the same program
            # would hash to two different compile-cache keys depending on
            # where the weights came from (elastic/compile_cache.py keys
            # on the HLO text) — a peer's cache entry would never hit
            self.params = jax.device_put(self.params, jax.devices()[0])
        self._queue: "queue.Queue[Request]" = queue.Queue()
        #: head-of-line request waiting for KV blocks (paged mode)
        self._stalled: Optional[Request] = None
        self._slots: List[Optional[Request]] = [None] * batch_size
        self._rng = np.random.default_rng(rng_seed)

        self._reset_device_state()

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
        #: speculative-decode counters: DEVICE-side verification steps and
        #: draft tokens accepted (includes discarded end-of-request
        #: overshoot, so this measures verification efficiency, not exact
        #: emitted-token counts)
        self.spec_stats = {"steps": 0, "accepted": 0}

    def _param_shardings(self, params):
        """NamedSharding pytree mirroring ``params`` (a value or eval_shape
        tree; incl. int8 {"q","s"} leaves — "s" drops the contraction dim,
        keeping per-out-channel scales aligned with their sharded
        channels)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dstack_tpu.models import llama as llama_mod

        if self._is_moe:
            from dstack_tpu.models import moe as moe_mod

            expert_axis = ("expert"
                           if self.mesh.shape.get("expert", 1) > 1 else None)
            specs = moe_mod.param_specs(self.cfg, self._policy, expert_axis)
        else:
            specs = llama_mod.param_specs(self.cfg, self._policy)
        # Serving overrides vs the training specs:
        # - embed replicated: decode reads ONE row per token — a
        #   vocab-sharded table would make SPMD all-gather the whole table
        #   every dispatch (llama._embed_lookup docstring).  Big TP models
        #   are untied (or int8-tied with a separate head copy), so the
        #   logits matmul still shards via lm_head.
        specs["embed"] = P(None, None)
        if "lm_head" in params and "lm_head" not in specs:
            # untied head, or a tied model's int8 head copy (quantize_params)
            specs["lm_head"] = P(self._policy.fsdp_axis,
                                 self._policy.tensor_axis)

        def leaf(spec, value):
            if isinstance(value, dict) and "q" in value:
                dims = tuple(spec)
                s_spec = P(*(dims[:-2] + dims[-1:])) if len(dims) >= 2 else P()
                return {"q": NamedSharding(self.mesh, spec),
                        "s": NamedSharding(self.mesh, s_spec)}
            return NamedSharding(self.mesh, spec)

        return jax.tree.map(leaf, specs, params,
                            is_leaf=lambda x: isinstance(x, P))

    def _kv_sharding(self):
        """KV caches shard over KV heads.  Dense: dim 3 (the quantized
        scale tensors lack the trailing D dim — int4's packed "q4" leaf
        keeps it, just half as wide).  Paged: the last dim of every leaf,
        Hkv*D lanes (head-major, so a shard holds whole heads) or the Hkv
        scales."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = self._policy.tensor_axis
        scales = NamedSharding(self.mesh, P(None, None, None, t))
        full = scales if self.paged else NamedSharding(
            self.mesh, P(None, None, None, t, None))
        if not self.kv_quant:
            return full
        qk = "q4" if self.kv_quantize == "int4" else "q"
        return {qk: full, "s": scales}

    def _reset_device_state(self) -> None:
        """(Re-)allocate the KV cache and slot state.  Called at init and
        after a device-side decode failure (the decode jit donates the
        caches, so a raise mid-execution leaves them deleted)."""
        b = self.batch_size
        if self._hybrid is not None:
            # the two donated state trees are the latent pool and the
            # recurrent state, not a K and a V cache
            self._cache_k, self._cache_v = self._hybrid.init_state()
            if self.telemetry is not None:
                self.telemetry.record_recurrent_state_bytes(
                    self._hybrid.recurrent_state_bytes())
        else:
            self._alloc_kv_caches()
        if self.paged and isinstance(self._alloc, PrefixBlockAllocator):
            # the KV backing every cached key was just reallocated
            self._alloc.clear_cache()
        self._decode_consts = None  # cached device constants died with it
        self._pending = None        # in-flight window handles died with it
        self._chunking = {}         # mid-chunk prefill state died with it
        self._lengths = jnp.zeros((b,), jnp.int32)     # tokens in cache
        # host mirror of _lengths: _emit's bookkeeping must not pay a
        # device->host fetch per generated token (it dominated serving
        # throughput on remote-RPC backends)
        self._host_lengths = np.zeros((b,), np.int64)
        self._last_token = jnp.zeros((b,), jnp.int32)
        self._active = jnp.zeros((b,), jnp.bool_)
        #: on-device token history per slot (speculation's n-gram corpus)
        self._hist = jnp.zeros((b, self.max_len), jnp.int32)

    def _alloc_kv_caches(self) -> None:
        """The Llama family's K and V caches, zeroed: dense rows per slot,
        or the paged pool."""
        cfg, b = self.cfg, self.batch_size
        lead = ((cfg.num_layers, self._alloc.num_blocks, self._block_size)
                if self.paged else (cfg.num_layers, b, self.max_len))
        hkv = cfg.num_kv_heads
        scales = lead + (hkv,)

        def values(d: int):
            # the paged pool is stored in the form the decode kernel
            # reads: kv heads folded into the lane dim (_fold_heads)
            return lead + ((hkv * d,) if self.paged else (hkv, d))

        def mk_zeros():
            if self.kv_quantize == "int4":
                return {"q4": jnp.zeros(values(cfg.head_dim // 2), jnp.int8),
                        "s": jnp.zeros(scales, jnp.float32)}
            if self.kv_quant:
                return {"q": jnp.zeros(values(cfg.head_dim), jnp.int8),
                        "s": jnp.zeros(scales, jnp.float32)}
            return jnp.zeros(values(cfg.head_dim), cfg.dtype)

        if self.mesh is not None:
            # allocate sharded directly — never the full cache on one
            # device.  The jitted allocator is cached: a rebuild per
            # decode-failure recovery would re-trace for nothing.
            if getattr(self, "_cache_alloc", None) is None:
                self._cache_alloc = _named_jit(
                    mk_zeros, "kv_cache_alloc",
                    out_shardings=self._kv_sharding())
            self._cache_k = self._cache_alloc()
            self._cache_v = self._cache_alloc()
        else:
            self._cache_k = mk_zeros()
            self._cache_v = mk_zeros()

    # -- public API --------------------------------------------------------

    def submit(self, request: Request) -> Request:
        if self.draining:
            # belt for non-HTTP callers; the server's handlers 503 first
            raise EngineDraining("engine is draining; not admitting")
        if request.prefill is not None and self._pd_refusal:
            raise ValueError(self._pd_refusal)
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
                    with jax.profiler.TraceAnnotation("engine.wait_for_work"):
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
                # the decode jit donates the caches: if it raised after
                # donation, self._cache_k/_v point at deleted buffers and
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
        loop (≈1.5 ms/step-equivalent on the remote-dispatch bench backend,
        more than half the end-to-end step cost) overlap device compute.

        Admission (prefill) only ever happens when NO window is in flight:
        a prefill writes cache rows that an in-flight window's end-of-window
        bulk insert could clobber.  The overlap chain therefore breaks
        whenever a queued request could take a free slot, costing one
        non-overlapped window at request boundaries.

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
            nxt = None
            if not self._can_admit():
                # chunks chain on the donated cache behind the in-flight
                # window, and ahead of nxt
                spent, completed = self._advance_chunks(budget)
                budget -= spent
                if not completed:
                    nxt = self._dispatch_window(
                        self._pending["remaining_after"])
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
                with jax.profiler.TraceAnnotation("engine.chunk"):
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
        if self.paged:
            # paged chunks ride the suffix-prefill program (block
            # scatter + gathered-span attention) with prefix_len = rows
            # already in the slot's blocks
            logits, self._cache_k, self._cache_v = self._run_program(
                self._prefill_jit, ("prefix", cbucket),
                functools.partial(self._prefill_fn_prefix, cbucket),
                self.params, jnp.asarray(padded),
                jnp.int32(len(chunk)), jnp.int32(done),
                self._cache_k, self._cache_v,
                self._slot_target(
                    slot_id, jnp.asarray(self._tables_host[slot_id])))
        else:
            logits, self._cache_k, self._cache_v = self._run_program(
                self._prefill_jit, ("chunk", cbucket),
                functools.partial(self._prefill_fn_chunk, cbucket),
                self.params, jnp.asarray(padded),
                jnp.int32(len(chunk)), jnp.int32(done),
                self._cache_k, self._cache_v, jnp.int32(slot_id))
        st["done"] = done + len(chunk)
        if self.telemetry is not None:
            self.telemetry.record_prefill(len(chunk), cbucket)
            # keep the backlog gauge fresh even when every slot is
            # chunking (no decode window dispatches then)
            self.telemetry.record_prefill_backlog(self._chunk_backlog())
        if st["done"] >= len(tokens):
            st["logits"] = logits
            st["n"] = len(tokens)

    def _finish_chunked(self) -> None:
        """Activate slots whose final prefill chunk has completed: sample
        the first token from the chunk's logits and open the slot for
        decode windows (it joins the next dispatched window)."""
        for slot_id, st in list(self._chunking.items()):
            if "logits" not in st:
                continue
            del self._chunking[slot_id]
            req = self._slots[slot_id]
            if req is None:
                continue
            n = st["n"]
            if self.prefix_cache:
                # publish the completed prompt's full blocks for future
                # prefix reuse (mirrors _prefill's publication)
                blocks = self._slot_blocks[slot_id]
                for i, bkey in enumerate(self._slot_prefix[slot_id][1]):
                    if (i + 1) * self._block_size <= n and i < len(blocks):
                        self._alloc.register(bkey, blocks[i])
            with jax.profiler.TraceAnnotation("engine.chunk"):
                first = self._sample_first(st["logits"], req)
                self._slots_gen += 1
                self._lengths = self._lengths.at[slot_id].set(n)
                self._host_lengths[slot_id] = n
                self._last_token = self._last_token.at[slot_id].set(first)
                self._active = self._active.at[slot_id].set(True)
                self._record_history(slot_id, st["tokens"], first)
                self._emit(slot_id, req, first)

    def _can_admit(self) -> bool:
        """A waiting request could take a free slot."""
        return ((self._stalled is not None or not self._queue.empty())
                and any(s is None for s in self._slots))

    def _admit(self) -> None:
        """Admit queued requests into free slots, under one
        ``engine.admit`` span per call that has both."""
        if not self._can_admit():
            return
        with jax.profiler.TraceAnnotation("engine.admit"):
            self._admit_into_free_slots()

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
                    if req.prefill is not None:
                        with jax.profiler.TraceAnnotation("engine.prefill"):
                            self._insert_prefilled(slot_id, req)
                    elif (self.prefill_chunk is not None
                          and self._prompt_len(req) > self.prefill_chunk):
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
                        with jax.profiler.TraceAnnotation("engine.prefill"):
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
                if self.speculation:
                    # baseline for the decode span's spec-accept attrs
                    req._spec0 = (self.telemetry.spec_steps.value,
                                  self.telemetry.spec_accepted.value)

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
        return maybe_cached(_named_jit(fn, tag, **jit_kwargs),
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
        with jax.profiler.TraceAnnotation("engine.build_program"):
            fn = table[key] = make()
            if self.telemetry is not None:
                self.telemetry.record_program_built(
                    "decode" if table is self._decode_jit else "prefill")
            return fn(*args)

    def _prefill_fn(self, bucket: int):
        cfg = self.cfg

        def fn(params, tokens, length, cache_k, cache_v, slot):
            # tokens: [bucket] padded; length: scalar actual prompt length
            logits, ks, vs = _prompt_forward(params, cfg, tokens, length,
                                             bucket)

            # insert prompt K/V into the slot: [L, bucket, Hkv, D] -> cache
            def insert(leaf, rows):
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    leaf, rows[:, None], start)

            with jax.named_scope("kv_insert"):
                cache_k = _kv_map(cache_k, ks[:, 0], insert)
                cache_v = _kv_map(cache_v, vs[:, 0], insert)
            return logits, cache_k, cache_v

        return self._jit_cached(fn, f"prefill_b{bucket}",
                                donate_argnums=(3, 4))

    def _prefill_fn_prefix(self, sbucket: int):
        """Suffix prefill against a cached prefix (prefix-cache mode).

        The slot's leading ``prefix_len`` positions already hold valid KV
        (reused blocks); this computes KV only for the suffix tokens —
        each layer scatters the suffix K/V into the slot's blocks, then
        attends the suffix queries over the gathered full span with
        absolute positions (RoPE phases match the cached prefix's).
        """
        if self._hybrid is not None:
            return self._jit_cached(self._hybrid.chunk_fn(sbucket),
                                    f"prefill_prefix_b{sbucket}",
                                    donate_argnums=(4, 5))
        cfg = self.cfg
        bs = self._block_size
        bps = self._blocks_per_slot
        kv_span = bps * bs

        def fn(params, suffix_tokens, suffix_len, prefix_len,
               cache_k, cache_v, tables_row):
            positions = prefix_len + jnp.arange(sbucket)[None, :]
            inv_freqs = jnp.asarray(rope_frequencies(
                cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
            x = params["embed"].astype(cfg.dtype)[suffix_tokens][None, :, :]
            kv_pos = jnp.arange(kv_span)[None, :]
            idx = prefix_len + jnp.arange(sbucket)
            # padding rows past the span write to the NULL block
            safe = idx < kv_span
            blk = jnp.where(
                safe, tables_row[jnp.clip(idx // bs, 0, bps - 1)], 0)
            off = idx % bs
            # MoE: padding must not claim expert capacity
            token_mask = (jnp.arange(sbucket) < suffix_len)[None, :]

            nb = self._alloc.num_blocks

            def layer(carry, inputs):
                # the pool travels in the carry and is addressed at
                # [layer, block, offset] by flat row: scanned as xs/ys it
                # would be sliced and restacked, a copy of the layer's
                # whole pool each way
                x, pool_k, pool_v = carry
                lp, l = inputs
                scatter = lambda leaf, rows: _scatter_rows(
                    leaf, (l * nb + blk) * bs + off, rows[0])
                gather = lambda pool: _split_heads(jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:])[
                        l * nb + tables_row].reshape(
                            1, kv_span, a.shape[-1]), pool),
                    cfg.num_kv_heads)
                x, pool_k, pool_v = _suffix_layer(
                    x, lp, cfg, positions, inv_freqs, kv_pos, token_mask,
                    pool_k, pool_v, scatter, gather, lanes=True)
                return (x, pool_k, pool_v), None

            (x, cache_k, cache_v), _ = jax.lax.scan(
                layer, (x, cache_k, cache_v),
                (params["layers"], jnp.arange(cfg.num_layers)))
            logits = _last_logits(params, cfg, x, suffix_len)
            return logits, cache_k, cache_v

        return self._jit_cached(fn, f"prefill_prefix_b{sbucket}",
                                donate_argnums=(4, 5))

    def _prefill_fn_chunk(self, cbucket: int):
        """One chunk of a long prompt against the DENSE cache: computes the
        chunk's K/V, writes it at the slot's rows [prefix_len, prefix_len +
        chunk), and attends the chunk's queries over everything the slot
        holds so far (earlier chunks + causal within this one).  RoPE uses
        absolute positions, so the result is bit-identical in structure to
        a whole-prompt prefill.  Returns last-position logits (meaningful
        on the final chunk only)."""
        cfg = self.cfg
        span = self.max_len

        def fn(params, chunk_tokens, chunk_len, prefix_len,
               cache_k, cache_v, slot):
            positions = prefix_len + jnp.arange(cbucket)[None, :]
            inv_freqs = jnp.asarray(rope_frequencies(
                cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
            x = params["embed"].astype(cfg.dtype)[chunk_tokens][None, :, :]
            kv_pos = jnp.arange(span)[None, :]
            token_mask = (jnp.arange(cbucket) < chunk_len)[None, :]
            # write targets: real chunk rows land at their positions;
            # bucket-padding rows (and any row past max_len — a final
            # chunk's bucket can overshoot it) are pushed out of range and
            # DROPPED, never clamped onto earlier valid rows
            row_idx = jnp.where(jnp.arange(cbucket) < chunk_len,
                                prefix_len + jnp.arange(cbucket), span)

            def insert(leaf, rows):
                # rows: [1, cbucket, ...] -> slot's rows, row_idx-mapped
                return leaf.at[slot, row_idx].set(rows[0], mode="drop")

            def gather(layer_kv):
                return jax.tree.map(
                    lambda leaf: jax.lax.dynamic_index_in_dim(
                        leaf, slot, 0, keepdims=True), layer_kv)

            def layer(carry, inputs):
                x = carry
                lp, layer_k, layer_v = inputs
                x, layer_k, layer_v = _suffix_layer(
                    x, lp, cfg, positions, inv_freqs, kv_pos, token_mask,
                    layer_k, layer_v, insert, gather)
                return x, (layer_k, layer_v)

            x, (cache_k, cache_v) = jax.lax.scan(
                layer, x, (params["layers"], cache_k, cache_v))
            logits = _last_logits(params, cfg, x, chunk_len)
            return logits, cache_k, cache_v

        return self._jit_cached(fn, f"prefill_chunk_b{cbucket}",
                                donate_argnums=(4, 5))

    def _prefill_fn_paged(self, bucket: int):
        if self._hybrid is not None:
            return self._jit_cached(self._hybrid.prefill_fn(bucket),
                                    f"prefill_paged_b{bucket}",
                                    donate_argnums=(3, 4))
        cfg = self.cfg
        bs = self._block_size
        nblk = bucket // bs

        def fn(params, tokens, length, cache_k, cache_v, bids):
            # bids: [nblk] physical block ids owned by the slot
            logits, ks, vs = _prompt_forward(params, cfg, tokens, length,
                                             bucket)

            def insert(leaf, rows):
                # the new rows take the pool's blocked form, never the
                # pool theirs: whole blocks, every layer, in place
                blocked = rows.reshape(
                    (cfg.num_layers, nblk, bs) + rows.shape[2:])
                return leaf.at[:, bids].set(blocked)

            with jax.named_scope("kv_insert"):
                cache_k = _kv_map(cache_k, ks[:, 0], insert, lanes=True)
                cache_v = _kv_map(cache_v, vs[:, 0], insert, lanes=True)
            return logits, cache_k, cache_v

        return self._jit_cached(fn, f"prefill_paged_b{bucket}",
                                donate_argnums=(3, 4))

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
            logits, self._cache_k, self._cache_v = self._run_program(
                self._prefill_jit, ("prefix", sbucket),
                functools.partial(self._prefill_fn_prefix, sbucket),
                self.params, jnp.asarray(padded),
                jnp.int32(n - prefix_len), jnp.int32(prefix_len),
                self._cache_k, self._cache_v,
                jnp.asarray(self._tables_host[slot_id]),
            )
        else:
            bucket = self._bucket(n)
            padded = np.zeros((bucket,), np.int32)
            padded[:n] = tokens[:bucket]
            target = (self._slot_target(slot_id, jnp.asarray(
                self._slot_blocks[slot_id][:bucket // self._block_size],
                jnp.int32)) if self.paged else slot_id)
            logits, self._cache_k, self._cache_v = self._run_program(
                self._prefill_jit, ("paged", bucket) if self.paged else bucket,
                functools.partial(self._prefill_fn_paged if self.paged
                                  else self._prefill_fn, bucket),
                self.params, jnp.asarray(padded), jnp.int32(n),
                self._cache_k, self._cache_v, target,
            )
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
            self.telemetry.record_prefill(n - prefix_len,
                                          self._bucket(n - prefix_len))
        first = self._sample_first(logits, req)
        self._slots[slot_id] = req
        self._slots_gen += 1
        self._lengths = self._lengths.at[slot_id].set(n)
        self._host_lengths[slot_id] = n
        self._last_token = self._last_token.at[slot_id].set(first)
        self._active = self._active.at[slot_id].set(True)
        self._record_history(slot_id, tokens, first)
        self._emit(slot_id, req, first)

    def _record_history(self, slot_id: int, tokens, first: int) -> None:
        """Seed the slot's on-device token history (speculation's n-gram
        corpus): the prompt at positions [0, n), the first generated token
        at n.  Whole-row write so a reused slot can't leak its previous
        occupant's tokens into drafts."""
        if not self.speculation:
            return
        n = min(len(tokens), self.max_len - 2)
        padded = np.zeros((self.max_len,), np.int32)
        padded[:n] = tokens[:n]
        padded[n] = first
        self._hist = self._hist.at[slot_id].set(jnp.asarray(padded))

    def prefill_export(self, tokens: List[int],
                       max_new_tokens: int = 128) -> dict:
        """PD disaggregation, prefill side: compute the prompt's KV and the
        last-position logits WITHOUT occupying a slot; the result ships to
        a decode replica (serving/server.py serializes it).  The prompt
        budget mirrors _prefill's (max_len - max_new_tokens - 1) so the
        disaggregated path truncates exactly like a colocated one.

        Parity role: the prefill worker half of the reference's SGLang PD
        integration — on TPU the KV rides the router instead of a
        bootstrap-port side channel.
        """
        if self._pd_refusal:
            raise ValueError(self._pd_refusal)
        cfg = self.cfg
        max_new_tokens = max(min(max_new_tokens, self.max_len - 2), 1)
        toks = self._prompt_tokens(tokens, max_new_tokens)
        n = len(toks)
        bucket = self._bucket(n)

        def fn(params, padded, length):
            logits, ks, vs = _prompt_forward(params, cfg, padded, length,
                                             bucket)
            return logits, ks[:, 0], vs[:, 0]  # [L, bucket, Hkv, D]

        padded = np.zeros((bucket,), np.int32)
        padded[:n] = toks[:bucket]
        logits, ks, vs = self._run_program(
            self._prefill_jit, ("export", bucket),
            lambda: self._jit_cached(fn, f"prefill_export_b{bucket}"),
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
        n = int(p["length"])
        # a prefill replica configured with a larger max_len must not be
        # able to crash this engine: keep the newest rows that fit
        limit = self.max_len - 2
        ks_np, vs_np = p["ks"], p["vs"]
        if n > limit:
            ks_np = ks_np[:, n - limit:]
            vs_np = vs_np[:, n - limit:]
            n = limit
        if self.paged:
            # pad to whole blocks, scatter into the slot's physical blocks
            cfg, bs = self.cfg, self._block_size
            nblk = -(-n // bs)
            pad = nblk * bs - n
            ks_np = np.pad(ks_np, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vs_np = np.pad(vs_np, ((0, 0), (0, pad), (0, 0), (0, 0)))
            bids = jnp.asarray(self._slot_blocks[slot_id][:nblk], jnp.int32)

            def insert(leaf, rows):
                blocked = rows.reshape(
                    (cfg.num_layers, nblk, bs) + rows.shape[2:])
                return leaf.at[:, bids].set(blocked)

        else:
            def insert(leaf, rows):
                start = (0, slot_id) + (0,) * (leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(leaf, rows[:, None], start)

        ks = jnp.asarray(ks_np, dtype=self.cfg.dtype)  # [L, rows, Hkv, D]
        vs = jnp.asarray(vs_np, dtype=self.cfg.dtype)
        self._cache_k = _kv_map(self._cache_k, ks, insert, self.paged)
        self._cache_v = _kv_map(self._cache_v, vs, insert, self.paged)
        if p.get("logits") is not None:
            # request-aware first token (temperature/top_p/top_k honored;
            # PD-wire logits arrive as numpy — asarray is host->device)
            first = self._sample_first(jnp.asarray(p["logits"]), req)
        else:
            first = int(p["first_token"])
        self._slots[slot_id] = req
        self._slots_gen += 1
        self._lengths = self._lengths.at[slot_id].set(n)
        self._host_lengths[slot_id] = n
        self._last_token = self._last_token.at[slot_id].set(first)
        self._active = self._active.at[slot_id].set(True)
        self._record_history(
            slot_id, self._prompt_tokens(req.tokens, req.max_new_tokens)[:n],
            first)
        self._emit(slot_id, req, first)

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

    def _decode_window_fn_buffered(self, params, last_token, lengths, active,
                                   cache_k, cache_v, temps, top_ps, top_ks,
                                   tables, rng, *, window: int,
                                   sampling: bool = True,
                                   kv_blocks: Optional[int] = None):
        """Decode window with a write-once cache (dense AND paged).

        The classic formulation (removed r4; see ROOFLINE.md for the A/B
        numbers) rewrote the whole [L, B, S] KV cache every step with a
        masked multiply-add — ~45% of the decode step's non-weight HBM
        traffic at the bench shape.  Here the big cache is READ-ONLY for
        the whole window: each step's K/V goes into a small [L, W] window
        buffer, attention runs over (cache ⧺ window prefix), and the cache
        absorbs all W rows in ONE pass at the end — full-cache write cost
        amortized 1/W.  Same logical attention set per step.

        Paged mode gets a second, larger win from the same invariance: the
        block-table gather (each slot's blocks → a linear KV view) happens
        ONCE per window instead of once per step — at long max_len that
        gather dominated the per-step formulation (22.4 → 8.2 ms/step at a
        4k span).

        RAGGED lengths (``kv_blocks``): the dispatcher passes a
        power-of-two bucket of table columns covering the longest active
        slot through the END of this window, so short sequences stop
        paying max_len-sized gathers and attention — the linear view (and
        its peak-memory allowance) shrinks from [L, B, blocks_per_slot*bs]
        to [L, B, kv_blocks*bs].  Columns a shorter slot doesn't own are
        cache_mask'ed exactly like the full span's, so the bucketed
        program emits the same tokens.

        On a TPU backend the gather disappears entirely: the Pallas
        block-table kernel (ops/flash_attention.py paged_decode_attention)
        reads K/V blocks straight from the paged pool via scalar-prefetched
        tables and returns a normalized (o, lse) pair per slot; the window
        buffer's attention merges with it by logsumexp, so no
        dense-equivalent linear view is ever materialized
        (DSTACK_TPU_PAGED_ATTN_KERNEL, auto = TPU only; int4 caches use
        the XLA path — the kernel dequantizes int8 in-kernel).
        """
        cfg = self.cfg
        b = self.batch_size
        w = window
        nbk = (kv_blocks or self._blocks_per_slot) if self.paged else 0
        kv_span = nbk * self._block_size if self.paged else self.max_len
        use_kernel = (self.paged and self._paged_kernel
                      and self.kv_quantize != "int4")
        inv_freqs = jnp.asarray(
            rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
        kv_index = jnp.arange(kv_span)[None, :]  # [1, S]
        head = output_head(params, cfg)
        base_len = jnp.minimum(lengths, self.max_len - 1)  # frozen for the window
        hkv, group = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
        # cache rows valid for every step of this window (window rows are
        # attended from the buffer instead)
        cache_mask = (kv_index < base_len[:, None])[:, None, None, :]
        if use_kernel:
            # the kernel reads blocks in place through the table, out of
            # the stored pool: the layer scan carries the layer's INDEX and
            # the kernel closes over the whole pool — no linear view, no
            # gather, and no per-layer slice of the pool (a scanned pool is
            # sliced into a buffer of its own for the custom call: a copy
            # of the layer's whole K and V pool every layer-step)
            layer_kv = jnp.arange(cfg.num_layers)
        elif self.paged:
            # one gather for the whole window: [L, B, span, ...] linear
            # views of each slot's blocks (read-only until the final
            # insert; quantized caches gather the packed bytes — half
            # (int8) or a quarter (int4) of the bf16 traffic); the heads
            # unfold on the gathered view, not on the pool
            def gather_view(cache):
                return _split_heads(jax.tree.map(
                    lambda a: a[:, tables].reshape(
                        cfg.num_layers, b, kv_span, a.shape[-1]), cache),
                    hkv)

            layer_kv = (gather_view(cache_k), gather_view(cache_v))
        else:
            layer_kv = (cache_k, cache_v)

        if use_kernel:
            from dstack_tpu.ops.flash_attention import (
                paged_decode_attention as paged_attn,
            )

            if self.mesh is not None:
                # a Pallas call is opaque to GSPMD: run it per device over
                # the kv-head shards the cache already has (_kv_sharding)
                from jax.sharding import PartitionSpec as P

                t = self._policy.tensor_axis
                heads = P(None, t, None, None)    # q, o: [B, Hkv, G, D]
                pages = P(None, None, None, t)    # every leaf of the pool
                if self.kv_quant:
                    pages = {"q": pages, "s": pages}
                paged_attn = jax.shard_map(
                    paged_attn, mesh=self.mesh,
                    in_specs=(heads, pages, pages, P(), P(), P()),
                    out_specs=(heads, P(None, t, None)), check_vma=False)

        win_shape = (cfg.num_layers, w, b, hkv, cfg.head_dim)
        win_k0 = jnp.zeros(win_shape, cfg.dtype)
        win_v0 = jnp.zeros(win_shape, cfg.dtype)
        win_j = jnp.arange(w)

        def one_step(carry, inputs):
            last_token, step_lengths, win_k, win_v = carry
            i, step_rng = inputs
            positions = jnp.minimum(step_lengths, self.max_len - 1)[:, None]
            x = params["embed"].astype(cfg.dtype)[last_token][:, None, :]
            # window cols visible at step i: j <= i (their positions are
            # base_len + j per slot)
            win_mask = (win_j[None, :] <= i)[:, None, None, :]  # [1,1,1,W]

            def layer(carry, inputs):
                x = carry
                lp, kv, wk, wv = inputs
                q, k, v = _decode_qkv(x, lp, cfg, positions, inv_freqs, b)
                # stash this step's K/V in the window buffer (small, in-place)
                wk = jax.lax.dynamic_update_index_in_dim(wk, k[:, 0], i, 0)
                wv = jax.lax.dynamic_update_index_in_dim(wv, v[:, 0], i, 0)
                qg = q.reshape(b, hkv, group, cfg.head_dim)
                scale = cfg.head_dim ** -0.5
                if use_kernel:
                    # cache half straight off the block table (normalized
                    # o + logsumexp per slot), window half in XLA, merged
                    # by logsumexp — numerically the same attention set,
                    # reduction order aside
                    with jax.named_scope("paged_attn"):
                        o_c, lse_c = paged_attn(
                            qg, cache_k, cache_v, kv, tables, base_len)
                    with jax.named_scope("attn"):
                        s_w = jnp.einsum("bhgd,jbhd->bhgj", qg, wk) * scale
                        s_w = jnp.where(win_mask, s_w,
                                        -1e30).astype(jnp.float32)
                        m_w = jnp.max(s_w, axis=-1)
                        p_w = jnp.exp(s_w - m_w[..., None])
                        l_w = jnp.sum(p_w, axis=-1)
                        o_w = jnp.einsum(
                            "bhgj,jbhd->bhgd", p_w.astype(x.dtype), wv
                        ).astype(jnp.float32) / l_w[..., None]
                        lse_w = m_w + jnp.log(l_w)
                        # empty-cache slots have lse_c = -inf; the window
                        # half always has column 0 visible, so lse is finite
                        lse = jnp.logaddexp(lse_c, lse_w)
                        attn = (o_c * jnp.exp(lse_c - lse)[..., None]
                                + o_w * jnp.exp(lse_w - lse)[..., None]
                                ).astype(x.dtype)
                else:
                    with jax.named_scope("attn"):
                        # quantized dequant fuses in
                        lk = _kv_mat(kv[0], x.dtype)
                        lv = _kv_mat(kv[1], x.dtype)
                        s_c = jnp.einsum("bhgd,bkhd->bhgk", qg, lk) * scale
                        s_c = jnp.where(cache_mask, s_c, -1e30)
                        s_w = jnp.einsum("bhgd,jbhd->bhgj", qg, wk) * scale
                        s_w = jnp.where(win_mask, s_w, -1e30)
                        s = jnp.concatenate([s_c, s_w], axis=-1)
                        probs = jax.nn.softmax(
                            s.astype(jnp.float32), axis=-1).astype(x.dtype)
                        p_c, p_w = (probs[..., :kv_span],
                                    probs[..., kv_span:])
                        attn = (jnp.einsum("bhgk,bkhd->bhgd", p_c, lv)
                                + jnp.einsum("bhgj,jbhd->bhgd", p_w, wv))
                x = _decode_layer_tail(x, attn, lp, cfg, b)
                return x, (wk, wv)

            x, (win_k, win_v) = jax.lax.scan(
                layer, x, (params["layers"], layer_kv, win_k, win_v))
            with jax.named_scope("lm_head"):
                x = rms_norm(x, params["final_norm"], cfg.rms_eps)
                logits = qmatmul(x, head, cfg.dtype,
                                 preferred=jnp.float32)[:, 0]
            if sampling:
                tokens = self._sample_on_device(logits, temps, top_ps,
                                                top_ks, step_rng)
            else:
                with jax.named_scope("sample"):
                    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_lengths = jnp.where(active, step_lengths + 1, step_lengths)
            return (tokens, new_lengths, win_k, win_v), tokens

        (last, new_lengths, win_k, win_v), tokens_all = jax.lax.scan(
            one_step, (last_token, lengths, win_k0, win_v0),
            (jnp.arange(w), jax.random.split(rng, w)))

        if self.paged:
            # row-wise scatter of the W new rows into each slot's blocks
            # (positions base_len + j; overshoot past the span lands in the
            # NULL block like the classic path's clamped writes)
            bs = self._block_size
            pos = base_len[:, None] + win_j[None, :]            # [B, W]
            # inactive slots (released, or mid-chunked-prefill) must not
            # write: their window rows are junk and a chunked prefill may
            # be filling those cache rows concurrently
            safe = (pos < kv_span) & active[:, None]
            blk_col = jnp.clip(pos // bs, 0, nbk - 1)
            phys = jnp.where(
                safe, jnp.take_along_axis(tables, blk_col, axis=1), 0)
            off = pos % bs

            # win: [L, W, B, ...] -> rows of the pool by flat index, per
            # (l, b, j); masked rows collide in the NULL blocks, so the
            # indices are not unique
            idx = ((jnp.arange(cfg.num_layers)[:, None, None]
                    * self._alloc.num_blocks + phys[None]) * bs + off[None])

            @jax.named_scope("kv_window_write")
            def scatter(cache, win):
                return _kv_map(cache, win, lambda leaf, rows: _scatter_rows(
                    leaf, idx, jnp.moveaxis(rows, 1, 2)), lanes=True)

            cache_k = scatter(cache_k, win_k)
            cache_v = scatter(cache_v, win_v)
            return tokens_all, last, new_lengths, cache_k, cache_v

        # Dense: ONE bulk insert — cache position p takes window row
        # p - base_len wherever base_len <= p < base_len + W.
        widx = jnp.clip(kv_index - base_len[:, None], 0, w - 1)  # [B, S]
        in_window = ((kv_index >= base_len[:, None])
                     & (kv_index < base_len[:, None] + w)
                     & active[:, None])  # see the paged-scatter note
        cache_k = _dense_window_insert(cache_k, win_k, widx, in_window)
        cache_v = _dense_window_insert(cache_v, win_v, widx, in_window)
        return tokens_all, last, new_lengths, cache_k, cache_v

    def _decode_window_fn_spec(self, params, last_token, lengths, active,
                               cache_k, cache_v, hist, *, window: int,
                               k: int):
        """Greedy decode window with n-gram (prompt-lookup) speculation.

        Each scan step verifies ``k`` draft tokens plus the real one in a
        single (k+1)-wide forward: drafts come from the latest bigram match
        in the slot's on-device token history (``hist``), the forward
        produces greedy continuations at all k+1 positions, and the
        longest draft prefix that matches is accepted — emitting 1..k+1
        tokens per step for the cost of one weight pass (decode is
        weight-read-bound, so the extra width is nearly free; with zero
        acceptance throughput matches the plain window).

        Static shapes despite variable acceptance: the window KV buffer
        has ``window*(k+1)`` columns whose validity lives in ``win_pos``
        ([B, cols], -1 = invalid).  Rows are written OPTIMISTICALLY before
        acceptance is known and retroactively invalidated — sound because
        a query at draft depth j is only USED when drafts 1..j were
        accepted, in which case every row it attended was real.  Accepted
        positions across steps are disjoint (step i+1 starts where step i
        accepted up to), so the end-of-window insert maps positions to
        columns uniquely.  Greedy only (acceptance is exact-match) and
        dense cache only; tokens match the plain window exactly in f32
        (tested over long acceptance-heavy generations) — in bf16 the
        widened forward's different reduction order can flip argmax
        near-ties, the same noise class as the paged-vs-dense programs.
        """
        cfg = self.cfg
        b = self.batch_size
        kv_span = self.max_len
        wc = window * (k + 1)
        inv_freqs = jnp.asarray(
            rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
        kv_index = jnp.arange(kv_span)[None, :]
        head = output_head(params, cfg)
        base_len = jnp.minimum(lengths, self.max_len - 1)
        hkv, group = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
        cache_mask = (kv_index < base_len[:, None])[:, None, None, None, :]
        view_k, view_v = cache_k, cache_v

        win_shape = (cfg.num_layers, wc, b, hkv, cfg.head_dim)
        win_k0 = jnp.zeros(win_shape, cfg.dtype)
        win_v0 = jnp.zeros(win_shape, cfg.dtype)
        win_pos0 = jnp.full((b, wc), -1, jnp.int32)
        jj = jnp.arange(k + 1)[None, :]

        def one_step(carry, i):
            last_token, cur_len, win_k, win_v, win_pos, hist = carry
            p0 = jnp.minimum(cur_len, kv_span - 1)
            # drafts: the k tokens that followed the LATEST earlier
            # occurrence of the current bigram (prev, last) in the history.
            # Invariant: hist[cur_len] == last_token (prefill seeds the
            # first token at n with lengths=n; window writes land at
            # positions+1), so the bigram's first element is
            # hist[cur_len-1]; earlier pairs start at p <= cur_len-2.
            prev_idx = jnp.clip(cur_len - 1, 0, kv_span - 1)
            prev = jnp.take_along_axis(hist, prev_idx[:, None], 1)[:, 0]
            pos_r = jnp.arange(kv_span - 1)[None, :]
            m = ((hist[:, :-1] == prev[:, None])
                 & (hist[:, 1:] == last_token[:, None])
                 & (pos_r < (cur_len - 1)[:, None]))
            found = m.any(axis=1) & (cur_len >= 2)
            p = (kv_span - 2) - jnp.argmax(m[:, ::-1], axis=1)
            didx = p[:, None] + 2 + jnp.arange(k)[None, :]
            draft_ok = found[:, None] & (didx < cur_len[:, None])
            drafts = jnp.take_along_axis(
                hist, jnp.clip(didx, 0, kv_span - 1), 1)
            drafts = jnp.where(draft_ok, drafts, -1)  # -1 never accepted
            tokens_in = jnp.concatenate(
                [last_token[:, None], jnp.maximum(drafts, 0)], axis=1)
            positions = p0[:, None] + jj                    # [B, k+1]
            positions_c = jnp.minimum(positions, kv_span - 1)
            x = params["embed"].astype(cfg.dtype)[tokens_in]  # [B, k+1, D]
            col0 = i * (k + 1)
            # optimistic validity: every row of this step, unless past the
            # cache span
            step_pos = jnp.where(positions < kv_span, positions, -1)
            win_pos = jax.lax.dynamic_update_slice(win_pos, step_pos,
                                                   (0, col0))
            qpos = positions

            def layer(carry, inputs):
                x = carry
                lp, layer_k, layer_v, wk, wv = inputs
                q, kk, vv = _decode_qkv(x, lp, cfg, positions_c, inv_freqs,
                                        b, m=k + 1)
                wk = jax.lax.dynamic_update_slice(
                    wk, kk.transpose(1, 0, 2, 3), (col0, 0, 0, 0))
                wv = jax.lax.dynamic_update_slice(
                    wv, vv.transpose(1, 0, 2, 3), (col0, 0, 0, 0))
                qg = q.reshape(b, k + 1, hkv, group, cfg.head_dim)
                scale = cfg.head_dim ** -0.5
                lk = _kv_mat(layer_k, x.dtype)
                lv = _kv_mat(layer_v, x.dtype)
                s_c = jnp.einsum("bqhgd,bkhd->bhgqk", qg, lk) * scale
                s_c = jnp.where(cache_mask, s_c, -1e30)
                s_w = jnp.einsum("bqhgd,wbhd->bhgqw", qg, wk) * scale
                w_mask = ((win_pos[:, None, None, None, :] >= 0)
                          & (win_pos[:, None, None, None, :]
                             <= qpos[:, None, None, :, None]))
                s_w = jnp.where(w_mask, s_w, -1e30)
                s = jnp.concatenate([s_c, s_w], axis=-1)
                probs = jax.nn.softmax(
                    s.astype(jnp.float32), axis=-1).astype(x.dtype)
                p_c, p_w = probs[..., :kv_span], probs[..., kv_span:]
                attn = (jnp.einsum("bhgqk,bkhd->bqhgd", p_c, lv)
                        + jnp.einsum("bhgqw,wbhd->bqhgd", p_w, wv))
                x = _decode_layer_tail(x, attn, lp, cfg, b, m=k + 1)
                return x, (wk, wv)

            x, (win_k, win_v) = jax.lax.scan(
                layer, x, (params["layers"], view_k, view_v, win_k, win_v))
            with jax.named_scope("lm_head"):
                x = rms_norm(x, params["final_norm"], cfg.rms_eps)
                logits = qmatmul(x, head, cfg.dtype, preferred=jnp.float32)
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k+1]
            match = (drafts == greedy[:, :k])
            n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), axis=1)
            n_acc = jnp.where(active, n_acc, 0)
            # retro-invalidate: draft rows past the accepted prefix, and
            # every row of inactive slots
            step_valid = ((jj <= n_acc[:, None]) & (step_pos >= 0)
                          & active[:, None])
            win_pos = jax.lax.dynamic_update_slice(
                win_pos, jnp.where(step_valid, step_pos, -1), (0, col0))
            # emitted tokens enter the history at positions+1 (each greedy
            # token CONTINUES the position it was predicted at)
            wpos = jnp.where(step_valid & (positions + 1 < kv_span),
                             positions + 1, kv_span)  # kv_span = dropped
            hist = hist.at[jnp.arange(b)[:, None], wpos].set(
                greedy, mode="drop")
            new_last = jnp.take_along_axis(greedy, n_acc[:, None], 1)[:, 0]
            new_last = jnp.where(active, new_last, last_token)
            cur_len = cur_len + jnp.where(active, n_acc + 1, 0)
            return ((new_last, cur_len, win_k, win_v, win_pos, hist),
                    (greedy, n_acc))

        (last, new_lengths, win_k, win_v, win_pos, hist), (toks, accs) = \
            jax.lax.scan(
                one_step,
                (last_token, lengths, win_k0, win_v0, win_pos0, hist),
                jnp.arange(window))

        # end-of-window bulk insert, keyed by each column's position
        eq = kv_index[:, :, None] == win_pos[:, None, :]      # [B, S, Wc]
        in_window = eq.any(-1)
        widx = jnp.argmax(eq, axis=-1)                        # [B, S]
        cache_k = _dense_window_insert(cache_k, win_k, widx, in_window)
        cache_v = _dense_window_insert(cache_v, win_v, widx, in_window)
        return toks, accs, last, new_lengths, cache_k, cache_v, hist

    #: decode-window sizes; each compiles once.  The biggest window is the
    #: steady-state path (measured +37% aggregate tok/s over capping at 32
    #: on the remote-dispatch bench backend); the small ones avoid large
    #: overshoot on short tails.  Trade-off: streaming callbacks burst up
    #: to 64 tokens and a queued prompt waits up to one window for a slot —
    #: latency-sensitive deployments can override this class attribute.
    DECODE_WINDOWS = (8, 32, 64)

    #: fixed per-window dispatch overhead expressed in decode steps (host
    #: round-trip + emit loop ≈ 8 steps' device time on the bench backend);
    #: _pick_window weighs overshoot against this when splitting tails
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
        inflight = (self._pending["window"]
                    if self._pending is not None else 0)
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
        with jax.profiler.TraceAnnotation("engine.dispatch_window"):
            if self.speculation and not sampling:
                return self._dispatch_window_spec(remaining, window)
            return self._dispatch_window_plain(remaining, window, sampling)

    def _decode_window_program(self, window: int, sampling: bool,
                               nbk: Optional[int]):
        """The jitted plain decode window for one (window, sampling,
        table-bucket) key."""
        fn = (self._hybrid.decode_window_fn(window, sampling, nbk)
              if self._hybrid is not None else functools.partial(
                  self._decode_window_fn_buffered, window=window,
                  sampling=sampling, kv_blocks=nbk))
        return self._jit_cached(
            fn, f"decode_w{window}_s{int(sampling)}"
            + (f"_kb{nbk}" if nbk is not None else ""),
            donate_argnums=(4, 5))

    def _dispatch_window_plain(self, remaining: int, window: int,
                               sampling: bool):
        """Dispatch a plain (non-speculative) window: build its tables and
        per-slot constants, enqueue the program."""
        nbk = self._ragged_blocks(window) if self.paged else None

        # Host->device transfers are RPC round-trips on remote-dispatch
        # backends — per WINDOW they must be near zero, so everything below
        # is cached against the current slot assignment (an admission or
        # release bumps _slots_gen; table buckets cache per ragged width)
        # and rng only advances when sampling (greedy windows ignore it —
        # reuse one constant key).
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
        # a model with experts returns, last, the window's expert load
        tokens_all, self._last_token, self._lengths, \
            self._cache_k, self._cache_v, *expert_load = self._run_program(
                self._decode_jit, (window, sampling, nbk),
                functools.partial(self._decode_window_program, window,
                                  sampling, nbk),
                self.params, self._last_token, self._lengths, self._active,
                self._cache_k, self._cache_v, temps, top_ps, top_ks, tables,
                sub,
            )
        # snapshot which slots this window actually decodes for: by drain
        # time a mid-chunking slot may have finished its prefill (left
        # _chunking), but ITS rows in this window are still junk
        decoding = frozenset(
            slot_id for slot_id, req in enumerate(self._slots)
            if req is not None and slot_id not in self._chunking)
        pending = {"tokens": tokens_all, "window": window,
                   "remaining_after": remaining - window,
                   "decoding": decoding, "expert_load": expert_load}
        if self.telemetry is not None:
            self._record_dispatch(len(decoding), pending)
        return pending

    def _dispatch_window_spec(self, remaining: int, window: int):
        """Dispatch a speculative greedy window (see _decode_window_fn_spec).

        Bookkeeping difference vs the plain window: each step emits a
        VARIABLE 1..k+1 tokens per slot, so the drain walks the accepted
        counts, and remaining_after uses the guaranteed-minimum one token
        per step (over-dispatch past that is discarded overshoot, exactly
        like the plain window's)."""
        k = self.speculation_k

        def make():
            return self._jit_cached(
                functools.partial(self._decode_window_fn_spec,
                                  window=window, k=k),
                f"decode_spec_w{window}", donate_argnums=(4, 5, 6))

        toks, accs, self._last_token, self._lengths, \
            self._cache_k, self._cache_v, self._hist = self._run_program(
                self._decode_jit, ("spec", window), make,
                self.params, self._last_token, self._lengths, self._active,
                self._cache_k, self._cache_v, self._hist,
            )
        decoding = frozenset(
            slot_id for slot_id, req in enumerate(self._slots)
            if req is not None and slot_id not in self._chunking)
        pending = {"tokens": toks, "accepted": accs, "window": window,
                   "remaining_after": remaining - window,
                   "decoding": decoding, "spec": True}
        if self.telemetry is not None:
            self._record_dispatch(len(decoding), pending)
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

    def _record_dispatch(self, n_decoding: int, pending: dict) -> None:
        """Per-window telemetry at dispatch time (batch occupancy, KV
        utilization, queue depth) + the monotonic stamp the drain uses
        for inter-token latency.  Only called when telemetry is on."""
        t = self.telemetry
        if t is None:  # callers gate too; cheap belt for new call sites
            return
        t.record_window(n_decoding, self.batch_size)
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
        """Pull the in-flight window's tokens to the host and emit them —
        the ONE device->host sync per window."""
        p = self._pending
        if p is None:
            return
        self._pending = None
        with jax.profiler.TraceAnnotation("engine.pull"):
            tokens_np = np.asarray(p["tokens"])
            accs_np = (np.asarray(p["accepted"])  # [W, B]
                       if p.get("spec") else None)
        if accs_np is not None:
            # acceptance observability: operators tune speculation_k (or
            # turn speculation off) from this ratio — draft tokens accepted
            # per verification step, over decoding slots only
            cols = sorted(p["decoding"])
            if cols:
                steps_n = p["window"] * len(cols)
                accepted_n = int(accs_np[:, cols].sum())
                self.spec_stats["steps"] += steps_n
                self.spec_stats["accepted"] += accepted_n
                if self.telemetry is not None:
                    # same counters, recorder-side: acceptance rate lands
                    # on /metrics next to the latency histograms
                    self.telemetry.record_spec(steps_n, accepted_n)
        emitted = 0
        with jax.profiler.TraceAnnotation("engine.emit"):
            for step in range(p["window"]):
                for slot_id, req in enumerate(self._slots):
                    if req is None or slot_id not in p["decoding"]:
                        # finished mid-window (discard overshoot) or was
                        # still prefilling at DISPATCH time (this window
                        # carried junk for the slot even if its prefill
                        # has since finished)
                        continue
                    if accs_np is None:
                        self._host_lengths[slot_id] += 1  # mirrors device
                        emitted += 1
                        self._emit(slot_id, req,
                                   int(tokens_np[step, slot_id]))
                        continue
                    for j in range(int(accs_np[step, slot_id]) + 1):
                        if self._slots[slot_id] is None:
                            break  # finished mid-burst: drop the rest
                        self._host_lengths[slot_id] += 1
                        emitted += 1
                        self._emit(slot_id, req,
                                   int(tokens_np[step, slot_id, j]))
        if self.telemetry is not None and "t0" in p:
            self.telemetry.record_drain(
                emitted, time.perf_counter() - p["t0"], len(p["decoding"]),
                steps=p["window"], batch_size=self.batch_size)
            if p.get("expert_load"):
                self.telemetry.record_expert_load(
                    *np.asarray(p["expert_load"][0]).tolist())

    def _sample_first(self, logits, req: Request) -> int:
        """Sample a request's FIRST token with the same fused on-device
        sampler the decode windows use (:meth:`_sample_on_device`).

        This replaced a host-side numpy softmax/top-p sampler that pulled
        the full [V] logits vector to the host per admission — the last
        logits-sized device->host transfer outside the decode loop.  Now
        one int32 crosses the wire (the slot bookkeeping genuinely needs
        the token id on the host).  Greedy (temp<=0) is argmax on both
        the old and the fused path, so greedy first tokens are
        bit-identical; sampled ones are seed-deterministic through the
        engine's threaded ``jax.random`` key."""
        def fn(lg, temp, top_p, top_k, rng):
            return self._sample_on_device(
                lg[None, :], temp[None], top_p[None], top_k[None],
                rng)[0]

        if req.temperature > 0.0:
            self._rng_key, sub = jax.random.split(self._rng_key)
        else:
            sub = self._rng_key  # greedy ignores it; don't burn entropy
        return int(self._run_program(
            self._prefill_jit, "first_token",
            lambda: self._jit_cached(fn, "first_token_sample"),
            jnp.asarray(logits), jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k or 0), sub))

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
        self._release_host(slot_id)
        self._active = self._active.at[slot_id].set(False)
        self._lengths = self._lengths.at[slot_id].set(0)

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
