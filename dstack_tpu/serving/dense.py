"""The Llama family behind the engine's seam: what its memory is (K and V
rows, a dense row per slot or a paged pool; plain, int8 or int4), what a
layer computes (pre-norm attention with RoPE, then a dense SwiGLU or a
Mixtral-style routed MLP; a looped decoder's sandwich norms), how often the
layer stack runs (once, or ``cfg.ut_steps`` passes over the same weights,
each pass with cache layers of its own: ``models/ouro.py``), and the
programs the scheduler dispatches over them (whole-prompt prefill, one
chunk, a decode window, the PD wire's export and insert).

The engine (``serving/engine.py``) owns slots, blocks, queues, windows,
sampling and the order of dispatch; it hands this class's two state trees,
the K and the V cache, to every program as they are and names the programs
for the compile cache.  ``serving/hybrid.py`` answers the same calls for a
model whose memory is a recurrent state beside a latent pool.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models import ouro
from dstack_tpu.models.llama import (
    LlamaConfig,
    Params,
    init_params,
    output_head,
)
from dstack_tpu.ops.pool import scatter_rows as _scatter_rows
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.ops.rotary import apply_rope, rope_frequencies
from dstack_tpu.serving import paged_window
from dstack_tpu.serving.quant import (
    dequantize_kv,
    dequantize_kv4,
    qmatmul,
    quantize_kv,
    quantize_kv4,
    quantize_params,
)
from dstack_tpu.utils.jax_runtime import named_jit

logger = logging.getLogger(__name__)


# Device-side regions carry a jax.named_scope so that a profiler trace and an
# HLO dump say which part of a program an operation belongs to: qkv, attn,
# paged_attn, mlp, lm_head, sample, kv_insert (prefill's write of a prompt's
# K/V), kv_window_write (the decode window's one write at its end), and for a
# looped decoder loop_pass (one pass over the layer stack) and exit_gate.


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


def _layer_kv(params, cfg: LlamaConfig, x, positions, inv_freqs, length,
              token_mask=None):
    """Per-layer K/V for a full sequence — shared by prefill.
    ``token_mask`` [B, S] marks real (non-padding) tokens for MoE routing;
    ``length`` picks the row a looped decoder's passes hand on
    (:func:`_layer_passes`)."""
    b, s, _ = x.shape

    def layer(carry, inputs):
        (x,), (lp,) = carry, inputs
        q, k, v = _decode_qkv(x, lp, cfg, positions, inv_freqs, b, s)
        attn = paged_window.masked_attention(q, k, v, positions, positions)
        return (_layer_tail(x, attn, lp, cfg, token_mask),), (k, v)

    (x,), (ks, vs), states = _layer_passes(params, cfg, layer, (x,), (),
                                           length)
    return x, ks, vs, states  # ks/vs: [cache layers, B, S, Hkv, D]


def _layer_passes(params, cfg: LlamaConfig, layer, carry, xs, length=None):
    """The layer stack over ``carry``, as often as the model runs it.

    ``layer(carry, (lp, *xs_l))`` is a program's layer body, ``carry`` its
    scan carry (a tuple that starts with the activations x), ``xs`` what it
    scans beside the layers' weights, a leading dim of ``cfg.cache_layers``
    each.  Returns (carry, the layers' ys stacked per CACHE layer, states).
    What a layer updates in place (the paged pool, the decode window's
    buffer) belongs in ``carry`` with the cache-layer index in ``xs``: the
    carry passes through both scans as it stands, an ``xs``/``ys`` is
    sliced and stacked a layer (and a pass) at a time.

    A plain decoder scans its layers once and ``states`` is None.  A looped
    one (``cfg.ut_steps`` > 1) scans the SAME stacked weights once a pass,
    pass t over cache layers [t*L, (t+1)*L); the shared final norm ends
    every pass, and ``states`` [T, ..., D] holds each pass's normed output
    for :func:`_output_rows`: whole, or of a prefill's [1, S, D] the row at
    ``length`` - 1."""
    if cfg.ut_steps == 1:
        carry, ys = jax.lax.scan(layer, carry, (params["layers"],) + xs)
        return carry, ys, None
    passes = (cfg.ut_steps, cfg.num_layers)

    @jax.named_scope("loop_pass")
    def one_pass(carry, xs_t):
        (x, *rest), ys = jax.lax.scan(layer, carry,
                                      (params["layers"],) + xs_t)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return (x, *rest), (ys, x if length is None else x[0, length - 1])

    carry, (ys, states) = jax.lax.scan(one_pass, carry, jax.tree.map(
        lambda a: a.reshape(passes + a.shape[1:]), xs), length=passes[0])
    return carry, jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ys), states


def _output_rows(params, cfg: LlamaConfig, x, states):
    """The rows the head reads and, for a looped decoder, the pass each was
    taken from: the final norm of a plain stack's output ``x``, or (every
    pass's output is normed already) the pass the exit gate selects among
    ``states`` (:func:`_layer_passes`)."""
    if states is None:
        with jax.named_scope("lm_head"):
            return rms_norm(x, params["final_norm"], cfg.rms_eps), None
    return ouro.exit_select(params, cfg, states)


def _last_logits(params, cfg: LlamaConfig, x, length, states):
    """Logits at the last of ``length`` real positions of a [1, S, D]
    prefill activation (a looped decoder: of ``states``, that row of every
    pass)."""
    rows, _ = _output_rows(params, cfg, x, states)
    with jax.named_scope("lm_head"):
        head = output_head(params, cfg)
        if states is None:
            rows = rows[0, length - 1, :]
        return qmatmul(rows, head, cfg.dtype, preferred=jnp.float32)


def _prompt_forward(params, cfg: LlamaConfig, padded, length, bucket: int):
    """Forward over a padded prompt: (last-position logits, ks, vs).
    The single source of truth for prefill math — used by both the
    slot-inserting prefill jit and the PD export jit."""
    positions = jnp.arange(bucket)[None, :]
    inv_freqs = jnp.asarray(rope_frequencies(
        cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    x = params["embed"].astype(cfg.dtype)[padded][None, :, :]
    token_mask = (jnp.arange(bucket)[None, :] < length)
    x, ks, vs, states = _layer_kv(params, cfg, x, positions, inv_freqs,
                                  length, token_mask)
    return _last_logits(params, cfg, x, length, states), ks, vs


@jax.named_scope("qkv")
def _decode_qkv(x, lp, cfg: LlamaConfig, positions, inv_freqs, b: int,
                m: int = 1):
    """Per-token projections + RoPE — factored out so the dense and paged
    branches of the buffered decode (and the prefill programs) can never
    diverge numerically.  ``m`` is the tokens per row: 1 for decode, the
    bucket for a prefill."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = qmatmul(h, lp["wq"], cfg.dtype).reshape(
        b, m, cfg.num_heads, cfg.head_dim)
    k = qmatmul(h, lp["wk"], cfg.dtype).reshape(
        b, m, cfg.num_kv_heads, cfg.head_dim)
    v = qmatmul(h, lp["wv"], cfg.dtype).reshape(
        b, m, cfg.num_kv_heads, cfg.head_dim)
    return (apply_rope(q, positions, inv_freqs),
            apply_rope(k, positions, inv_freqs), v)


def _layer_tail(x, attn, lp, cfg: LlamaConfig, token_mask=None):
    """Post-attention half of a layer over [B, S, D] activations: wo and
    the MLP, each added to the residual, behind a norm of its own where the
    layer has one (a looped decoder's sandwich, ``models/ouro.py``)."""
    b, s, _ = x.shape
    branch = qmatmul(attn.reshape(b, s, cfg.q_dim), lp["wo"], cfg.dtype)
    if "attn_out_norm" in lp:
        branch = rms_norm(branch, lp["attn_out_norm"], cfg.rms_eps)
    x = x + branch
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    branch = _mlp_block(h, lp, cfg, token_mask)
    if "mlp_out_norm" in lp:
        branch = rms_norm(branch, lp["mlp_out_norm"], cfg.rms_eps)
    return x + branch


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
    one write the buffered formulation amortizes the whole window's cache
    updates into."""
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
    attn = paged_window.masked_attention(q, kv_k, kv_v, positions, kv_pos)
    return _layer_tail(x, attn, lp, cfg, token_mask), layer_k, layer_v



class DensePrograms:
    """The state and the programs of a ``models/llama.py`` or
    ``models/moe.py`` configuration (see the module docstring)."""

    #: prefill/decode disaggregation is served: the wire carries K and V rows
    pd_refusal: Optional[str] = None

    def __init__(self, cfg: LlamaConfig, *, batch_size: int, max_len: int,
                 paged: bool, block_size: int, num_blocks: int,
                 prefix_cache: bool, quantize: Optional[str],
                 kv_quantize: Optional[str], mesh: Optional[Any],
                 sharding_policy: Optional[Any], sample: Callable):
        """What the engine was built with (``InferenceEngine.__init__``
        documents each option); ``num_blocks`` is the paged pool's size and
        ``sample`` the engine's on-device sampler (logits, temps, top_ps,
        top_ks, rng)."""
        self.cfg = cfg
        self.batch_size = batch_size
        self.max_len = max_len
        self.paged = paged
        self.quantize = quantize
        self._sample = sample
        if prefix_cache and not paged:
            raise ValueError("prefix_cache requires paged=True (the cache "
                             "is block-addressed)")
        if kv_quantize not in (None, "int8", "int4"):
            raise ValueError(f"unsupported kv_quantize={kv_quantize!r} "
                             "(only 'int8' or 'int4')")
        if kv_quantize == "int4" and cfg.head_dim % 2:
            raise ValueError("int4 KV packing needs an even head_dim")
        self.kv_quantize = kv_quantize
        self.kv_quant = kv_quantize is not None
        #: Pallas block-table decode kernel (resolved once at init)
        self._paged_kernel = paged_window.paged_kernel_default()
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
            self.block_size = block_size
            self.num_blocks = num_blocks
            self.blocks_per_slot = max_len // block_size
            lanes = cfg.num_kv_heads * cfg.head_dim // t
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

    # -- parameters ----------------------------------------------------------
    def prepare_params(self, params: Optional[Params], rng_seed: int):
        """The weights as the programs take them: initialised from
        ``rng_seed`` when ``params`` is None, placed on the mesh (or
        committed to the one device), int8 where ``quantize`` says."""
        from dstack_tpu.models.moe import MoEConfig, init_params as moe_init

        cfg, mesh = self.cfg, self.mesh
        init = (moe_init if isinstance(cfg, MoEConfig)
                else ouro.init_params if isinstance(cfg, ouro.OuroConfig)
                else init_params)
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
                shapes = jax.eval_shape(
                    lambda: init(jax.random.PRNGKey(0), cfg))
                params = named_jit(
                    lambda: init(jax.random.PRNGKey(rng_seed), cfg),
                    "init_params",
                    out_shardings=self._param_shardings(shapes),
                )()
            else:
                params = init(jax.random.PRNGKey(rng_seed), cfg)
        elif mesh is not None:
            # host (numpy / checkpoint) arrays transfer shard-wise here;
            # already-committed device arrays get resharded
            params = jax.device_put(params, self._param_shardings(params))
        if self.quantize is not None:
            if self.quantize != "int8":
                raise ValueError(f"unsupported quantize={self.quantize!r} "
                                 "(only 'int8')")
            # weight-only int8 (serving/quant.py): decode is weight-read
            # bound, so int8 weights ~halve the per-step HBM floor; tied
            # models get an int8 COPY of the head so the logits matmul
            # (the single largest read) streams int8 too
            # under a mesh this runs on already-sharded arrays (executes
            # distributed); the device_put below only re-aligns the int8
            # scales and the tied-head copy
            params = quantize_params(
                params, tied_head_copy=cfg.tie_embeddings)
            if mesh is not None:
                params = jax.device_put(
                    params, self._param_shardings(params))
        if mesh is None:
            # commit the params: an UNcommitted tree lowers without
            # mhlo.sharding annotations while a checkpoint-restored
            # (committed) one carries "{replicated}", so the same program
            # would hash to two different compile-cache keys depending on
            # where the weights came from (elastic/compile_cache.py keys
            # on the HLO text) — a peer's cache entry would never hit
            params = jax.device_put(params, jax.devices()[0])
        return params

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
            model = (ouro if isinstance(self.cfg, ouro.OuroConfig)
                     else llama_mod)
            specs = model.param_specs(self.cfg, self._policy)
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

    # -- state ---------------------------------------------------------------
    def init_state(self):
        """``(cache_k, cache_v)``, zeroed: dense rows per slot, or the
        paged pool."""
        cfg, b = self.cfg, self.batch_size
        lead = ((cfg.cache_layers, self.num_blocks, self.block_size)
                if self.paged else (cfg.cache_layers, b, self.max_len))
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
                self._cache_alloc = named_jit(
                    mk_zeros, "kv_cache_alloc",
                    out_shardings=self._kv_sharding())
            return self._cache_alloc(), self._cache_alloc()
        return mk_zeros(), mk_zeros()

    def recurrent_state_bytes(self) -> int:
        """Bytes of state a slot holds whatever its length: none, every
        row of K and V belongs to a token."""
        return 0

    def kv_geometry(self) -> tuple:
        """(cache layers, bytes of K and V one token holds over them: int8
        and int4 rows carry a float32 scale a kv head)."""
        cfg = self.cfg
        head_bytes = {None: cfg.head_dim * jnp.dtype(cfg.dtype).itemsize,
                      "int8": cfg.head_dim + 4,
                      "int4": cfg.head_dim // 2 + 4}[self.kv_quantize]
        return cfg.cache_layers, (2 * cfg.cache_layers * cfg.num_kv_heads
                                  * head_bytes)

    def record_window_counts(self, telemetry, counts) -> None:
        """A drained window's last output, which only a looped decoder
        returns: the passes its steps ran, then its tokens by exit pass."""
        if telemetry is None:
            return
        passes, *exit_tokens = counts.tolist()
        telemetry.record_loop_passes(passes, exit_tokens)

    @staticmethod
    def record_prompt_program(telemetry, bucket: int) -> None:
        """A prefill or chunk program of ``bucket`` positions ran: nothing
        this family counts (Nemotron-H's, ``serving/mixed.py``, counts its
        scans)."""

    def slot_target(self, slot_id, pages):
        """Where a prefill or chunk program writes: the slot's ``pages``
        (block ids or its table row) when paged, its row of the cache
        otherwise."""
        return pages if self.paged else slot_id

    # -- prefill -------------------------------------------------------------
    def prefill_fn(self, bucket: int):
        """A whole prompt into an empty slot: ``fn(params, tokens [bucket],
        length, cache_k, cache_v, target)``."""
        return (self._prefill_fn_paged(bucket) if self.paged
                else self._prefill_fn(bucket))

    def chunk_fn(self, cbucket: int):
        """One chunk of a long prompt, or a prompt's suffix behind a cached
        prefix: ``fn(params, tokens [cbucket], chunk_len, prefix_len,
        cache_k, cache_v, target)``."""
        return (self._prefill_fn_prefix(cbucket) if self.paged
                else self._prefill_fn_chunk(cbucket))

    def _prefill_fn(self, bucket: int):
        cfg = self.cfg

        def fn(params, tokens, length, cache_k, cache_v, slot):
            # tokens: [bucket] padded; length: scalar actual prompt length
            logits, ks, vs = _prompt_forward(params, cfg, tokens, length,
                                             bucket)

            # insert prompt K/V into the slot: [cache layers, bucket, Hkv,
            # D] -> cache
            def insert(leaf, rows):
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    leaf, rows[:, None], start)

            with jax.named_scope("kv_insert"):
                cache_k = _kv_map(cache_k, ks[:, 0], insert)
                cache_v = _kv_map(cache_v, vs[:, 0], insert)
            return logits, cache_k, cache_v

        return fn

    def _prefill_fn_prefix(self, sbucket: int):
        """Suffix prefill against a cached prefix (prefix-cache mode).

        The slot's leading ``prefix_len`` positions already hold valid KV
        (reused blocks); this computes KV only for the suffix tokens —
        each layer scatters the suffix K/V into the slot's blocks, then
        attends the suffix queries over the gathered full span with
        absolute positions (RoPE phases match the cached prefix's).
        """
        cfg = self.cfg
        bs = self.block_size
        kv_span = self.blocks_per_slot * bs

        def fn(params, suffix_tokens, suffix_len, prefix_len,
               cache_k, cache_v, tables_row):
            positions = prefix_len + jnp.arange(sbucket)[None, :]
            inv_freqs = jnp.asarray(rope_frequencies(
                cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
            x = params["embed"].astype(cfg.dtype)[suffix_tokens][None, :, :]
            kv_pos = jnp.arange(kv_span)[None, :]
            blk, off = paged_window.chunk_pages(
                prefix_len, sbucket, tables_row, bs, kv_span)
            # MoE: padding must not claim expert capacity
            token_mask = (jnp.arange(sbucket) < suffix_len)[None, :]

            nb = self.num_blocks

            def layer(carry, inputs):
                # the pool travels in the carry and is addressed at
                # [cache layer, block, offset] by flat row: scanned as
                # xs/ys it would be sliced and restacked, a copy of the
                # layer's whole pool each way
                x, pool_k, pool_v = carry
                lp, l = inputs
                scatter = lambda leaf, rows: _scatter_rows(
                    leaf, paged_window.flat_rows(l, blk, off, nb, bs),
                    rows[0])
                gather = lambda pool: _split_heads(jax.tree.map(
                    lambda a: paged_window.slot_span(
                        a, l, tables_row, nb, lead=(1,)), pool),
                    cfg.num_kv_heads)
                x, pool_k, pool_v = _suffix_layer(
                    x, lp, cfg, positions, inv_freqs, kv_pos, token_mask,
                    pool_k, pool_v, scatter, gather, lanes=True)
                return (x, pool_k, pool_v), None

            (x, cache_k, cache_v), _, states = _layer_passes(
                params, cfg, layer, (x, cache_k, cache_v),
                (jnp.arange(cfg.cache_layers),), suffix_len)
            logits = _last_logits(params, cfg, x, suffix_len, states)
            return logits, cache_k, cache_v

        return fn

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
                (x,), (lp, layer_k, layer_v) = carry, inputs
                x, layer_k, layer_v = _suffix_layer(
                    x, lp, cfg, positions, inv_freqs, kv_pos, token_mask,
                    layer_k, layer_v, insert, gather)
                return (x,), (layer_k, layer_v)

            (x,), (cache_k, cache_v), states = _layer_passes(
                params, cfg, layer, (x,), (cache_k, cache_v), chunk_len)
            logits = _last_logits(params, cfg, x, chunk_len, states)
            return logits, cache_k, cache_v

        return fn

    def _prefill_fn_paged(self, bucket: int):
        cfg = self.cfg
        bs = self.block_size
        nblk = bucket // bs

        def fn(params, tokens, length, cache_k, cache_v, bids):
            # bids: [nblk] physical block ids owned by the slot
            logits, ks, vs = _prompt_forward(params, cfg, tokens, length,
                                             bucket)

            def insert(leaf, rows):
                # the new rows take the pool's blocked form, never the
                # pool theirs: whole blocks, every layer, in place
                blocked = rows.reshape(
                    (cfg.cache_layers, nblk, bs) + rows.shape[2:])
                return leaf.at[:, bids].set(blocked)

            with jax.named_scope("kv_insert"):
                cache_k = _kv_map(cache_k, ks[:, 0], insert, lanes=True)
                cache_v = _kv_map(cache_v, vs[:, 0], insert, lanes=True)
            return logits, cache_k, cache_v

        return fn

    # -- the PD wire ---------------------------------------------------------
    def export_fn(self, bucket: int):
        """PD disaggregation, prefill side: ``fn(params, padded [bucket],
        length)`` -> (last-position logits, ks, vs [L, bucket, Hkv, D]),
        no slot occupied."""
        cfg = self.cfg

        def fn(params, padded, length):
            logits, ks, vs = _prompt_forward(params, cfg, padded, length,
                                             bucket)
            return logits, ks[:, 0], vs[:, 0]  # [L, bucket, Hkv, D]

        return fn

    def insert_rows(self, cache_k, cache_v, prefill: dict, n: int, target):
        """PD disaggregation, decode side: the newest ``n`` rows of a
        prefill replica's K and V (``prefill["ks"]``/``["vs"]``: np [L,
        rows, Hkv, D]) into the slot at ``target`` (:meth:`slot_target`
        of its first ceil(n / block) blocks).  Returns the two caches."""
        # a prefill replica configured with a larger max_len must not be
        # able to crash this engine: keep the newest rows that fit
        ks_np, vs_np = prefill["ks"], prefill["vs"]
        ks_np = ks_np[:, ks_np.shape[1] - n:]
        vs_np = vs_np[:, vs_np.shape[1] - n:]
        if self.paged:
            # pad to whole blocks, scatter into the slot's physical blocks
            cfg, bs = self.cfg, self.block_size
            nblk = -(-n // bs)
            pad = nblk * bs - n
            ks_np = np.pad(ks_np, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vs_np = np.pad(vs_np, ((0, 0), (0, pad), (0, 0), (0, 0)))

            def insert(leaf, rows):
                blocked = rows.reshape(
                    (cfg.cache_layers, nblk, bs) + rows.shape[2:])
                return leaf.at[:, target].set(blocked)

        else:
            def insert(leaf, rows):
                start = (0, target) + (0,) * (leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(leaf, rows[:, None], start)

        ks = jnp.asarray(ks_np, dtype=self.cfg.dtype)  # [L, rows, Hkv, D]
        vs = jnp.asarray(vs_np, dtype=self.cfg.dtype)
        return (_kv_map(cache_k, ks, insert, self.paged),
                _kv_map(cache_v, vs, insert, self.paged))

    # -- decode --------------------------------------------------------------
    def decode_window_fn(self, window: int, sampling: bool,
                         kv_blocks: Optional[int]):
        """``window`` tokens for every active slot in one program:
        ``fn(params, last_token, lengths, active, cache_k, cache_v, temps,
        top_ps, top_ks, tables, rng)`` -> (tokens [window, B], last token,
        lengths, cache_k, cache_v)."""
        return functools.partial(self._decode_window_fn_buffered,
                                 window=window, sampling=sampling,
                                 kv_blocks=kv_blocks)

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

        The window buffer ([cache layers, W, B, Hkv, D], K's and V's) lives
        in the scans' CARRIES, never in their xs/ys: the step scan hands it
        to the layer scan (through a looped decoder's pass scan, too), a
        layer-step writes its one row at (cache layer, step) in place and
        reads its layer's slab inside the products.  A scan slices an xs
        operand into a buffer of its own and stacks its ys into a fresh
        one every iteration; for this buffer that was a third of a decode
        step on the chip (PERF.md section 6, PR 34), as it was for the
        paged pool before it.

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
        copies a slot's live pages out of the stored pool itself, a block
        of several pages a grid step by the scalar-prefetched tables, and
        returns a normalized (o, lse) pair per slot; the bucket bounds the
        grid, the lengths what is fetched.  The window buffer's attention
        merges with it by logsumexp, so no dense-equivalent linear view is
        ever materialized (DSTACK_TPU_PAGED_ATTN_KERNEL, auto = TPU only;
        int4 caches use the XLA path — the kernel reads int8 pages and
        applies their scales in-kernel).
        """
        cfg = self.cfg
        b = self.batch_size
        w = window
        nbk = (kv_blocks or self.blocks_per_slot) if self.paged else 0
        kv_span = nbk * self.block_size if self.paged else self.max_len
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
            # the stored pool: it closes over the whole pool and takes the
            # cache-layer INDEX every layer-step gets — no linear view, no
            # gather, and no per-layer slice of the pool (a scanned pool is
            # sliced into a buffer of its own for the custom call: a copy
            # of the layer's whole K and V pool every layer-step)
            layer_kv = None
        elif self.paged:
            # one gather for the whole window: [L, B, span, ...] linear
            # views of each slot's blocks (read-only until the final
            # insert; quantized caches gather the packed bytes — half
            # (int8) or a quarter (int4) of the bf16 traffic); the heads
            # unfold on the gathered view, not on the pool
            def gather_view(cache):
                return _split_heads(jax.tree.map(
                    lambda a: a[:, tables].reshape(
                        cfg.cache_layers, b, kv_span, a.shape[-1]), cache),
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

        # the window buffer: row (l, i) is step i's K (or V) at cache layer
        # l, carried through every scan (see the docstring).  The heads
        # stay a dim of their own: folded into lanes as the pool's are, a
        # layer's slab has to be un-folded for the products, and the TPU
        # compiler makes that a buffer (two at head_dim 64) every
        # layer-step (PERF.md section 6, PR 34)
        win_shape = (cfg.cache_layers, w, b, hkv, cfg.head_dim)
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
                (x, win_k, win_v), (lp, l, kv) = carry, inputs
                q, k, v = _decode_qkv(x, lp, cfg, positions, inv_freqs, b)
                # stash this step's K/V: row (l, i) of the carried buffer
                win_k = jax.lax.dynamic_update_slice(
                    win_k, k[:, 0][None, None], (l, i, 0, 0, 0))
                win_v = jax.lax.dynamic_update_slice(
                    win_v, v[:, 0][None, None], (l, i, 0, 0, 0))
                # the layer's [W, B, Hkv, D] slabs, each read by one product
                wk = jax.lax.dynamic_index_in_dim(win_k, l, 0, keepdims=False)
                wv = jax.lax.dynamic_index_in_dim(win_v, l, 0, keepdims=False)
                qg = q.reshape(b, hkv, group, cfg.head_dim)
                if use_kernel:
                    # cache half straight off the block table, window half
                    # in XLA, merged by logsumexp
                    attn = paged_window.attend_pages_and_window(
                        paged_attn, qg, cache_k, cache_v, l, tables,
                        base_len, wk, wv, win_mask, x.dtype)
                else:
                    with jax.named_scope("attn"):
                        # quantized dequant fuses in
                        lk = _kv_mat(kv[0], x.dtype)
                        lv = _kv_mat(kv[1], x.dtype)
                    attn = paged_window.attend_view_and_window(
                        qg, lk, lv, cache_mask, wk, wv, win_mask, x.dtype)
                return (_layer_tail(x, attn, lp, cfg), win_k, win_v), None

            (x, win_k, win_v), _, states = _layer_passes(
                params, cfg, layer, (x, win_k, win_v),
                (jnp.arange(cfg.cache_layers), layer_kv))
            x, exit_step = _output_rows(params, cfg, x, states)
            with jax.named_scope("lm_head"):
                logits = qmatmul(x, head, cfg.dtype,
                                 preferred=jnp.float32)[:, 0]
            if sampling:
                tokens = self._sample(logits, temps, top_ps,
                                                top_ks, step_rng)
            else:
                with jax.named_scope("sample"):
                    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_lengths = jnp.where(active, step_lengths + 1, step_lengths)
            # a looped decoder counts its step: the passes it ran and, by
            # exit pass, the active slots' tokens ([1 + T] float32)
            counts = None if states is None else jnp.concatenate([
                jnp.full((1,), states.shape[0], jnp.float32),
                jnp.sum((exit_step[:, 0, None] == jnp.arange(cfg.ut_steps))
                        & active[:, None], axis=0, dtype=jnp.float32)])
            return (tokens, new_lengths, win_k, win_v), (tokens, counts)

        (last, new_lengths, win_k, win_v), (tokens_all, counts) = \
            jax.lax.scan(
                one_step, (last_token, lengths, win_k0, win_v0),
                (jnp.arange(w), jax.random.split(rng, w)))
        # what the window returns behind its caches: nothing, or a looped
        # decoder's counts summed over its steps (record_window_counts)
        counts = () if counts is None else (counts.sum(0),)

        if self.paged:
            # row-wise scatter of the W new rows into each slot's blocks
            # (positions base_len + j): rows [L, W, B, ...] by flat index,
            # per (l, b, j)
            idx = paged_window.window_rows(
                cfg.cache_layers, base_len, active, tables, win_j,
                self.block_size, self.num_blocks)

            @jax.named_scope("kv_window_write")
            def scatter(cache, win):
                return _kv_map(cache, win, lambda leaf, rows: _scatter_rows(
                    leaf, idx, jnp.moveaxis(rows, 1, 2)), lanes=True)

            cache_k = scatter(cache_k, win_k)
            cache_v = scatter(cache_v, win_v)
            return (tokens_all, last, new_lengths, cache_k, cache_v, *counts)

        # Dense: ONE bulk insert — cache position p takes window row
        # p - base_len wherever base_len <= p < base_len + W.
        widx = jnp.clip(kv_index - base_len[:, None], 0, w - 1)  # [B, S]
        in_window = ((kv_index >= base_len[:, None])
                     & (kv_index < base_len[:, None] + w)
                     & active[:, None])  # see the paged-scatter note
        cache_k = _dense_window_insert(cache_k, win_k, widx, in_window)
        cache_v = _dense_window_insert(cache_v, win_v, widx, in_window)
        return (tokens_all, last, new_lengths, cache_k, cache_v, *counts)
