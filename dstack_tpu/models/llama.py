"""Llama-3 family, TPU-first.

Design choices (vs. a torch port):
- **Functional**: params are a plain pytree; the forward is a pure function —
  composes directly with jit/grad/shard_map and Orbax checkpointing.
- **Stacked layers + ``lax.scan``**: all transformer blocks share one set of
  stacked weights ([L, ...] leading dim), so compile time is O(1) in depth and
  XLA pipelines the layer loop.
- **Sharding is declared, not programmed**: :func:`param_specs` returns a
  PartitionSpec pytree (fsdp/tensor axes); activations get
  ``with_sharding_constraint`` at layer boundaries and XLA inserts the
  all-gathers/reduce-scatters (scaling-book recipe).
- **Long context**: set ``ShardingPolicy.seq_axis`` to shard the sequence dim;
  attention then runs context-parallel via shard_map — ring attention
  (ppermute over ICI) or Ulysses all-to-all, per ``seq_scheme``.

This is the serving/training workload the control plane exists to launch
(BASELINE.json: Llama-3-8B on v5e-64); the reference orchestrates such models
but does not implement them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from dstack_tpu.ops import flash_attention as flash
from dstack_tpu.ops.attention import KVCache, causal_attention, decode_step_attention
from dstack_tpu.ops.ring_attention import ring_attention_sharded
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.ops.rotary import RopeScaling, apply_rope, rope_frequencies

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500_000.0
    rope_scaling: Optional[RopeScaling] = None
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_70b(cls, **kw) -> "LlamaConfig":
        return cls(
            hidden_size=8192, intermediate_size=28_672, num_layers=80,
            num_heads=64, num_kv_heads=8, **kw,
        )

    @classmethod
    def llama3_8b_fit(cls, num_layers: int = 6, **kw) -> "LlamaConfig":
        """The Llama-3-8B LAYER GEOMETRY (hidden 4096, ffn 14336, GQA 32/8,
        head_dim 128) at a depth whose bf16 AdamW state fits one 16 GB v5e
        chip.  Full-depth 8B training state is ~48 GB — three chips of HBM —
        so the single-chip bench measures true 8B per-layer compute on this
        shape and extrapolates; multi-chip runs use llama3_8b() sharded."""
        return cls(num_layers=num_layers, tie_embeddings=True, **kw)

    @classmethod
    def llama3_1b(cls, **kw) -> "LlamaConfig":
        """Llama-3.2-1B shape — fits one v5e chip for bench/dev."""
        return cls(
            hidden_size=2048, intermediate_size=8192, num_layers=16,
            num_heads=32, num_kv_heads=8, head_dim=64, tie_embeddings=True,
            **kw,
        )

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test/dry-run config: small but structurally faithful (GQA etc.)."""
        return cls(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
            max_seq_len=256, **kw,
        )

    @property
    def ut_steps(self) -> int:
        """Passes over the layer stack: 1, but for a looped decoder
        (``models/ouro.py``, whose config makes this a field)."""
        return 1

    @property
    def cache_layers(self) -> int:
        """Layers of K/V a token holds: a pass attends its own."""
        return self.ut_steps * self.num_layers

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def num_params(self) -> int:
        embed = self.vocab_size * self.hidden_size
        attn = self.hidden_size * self.q_dim + 2 * self.hidden_size * self.kv_dim \
            + self.q_dim * self.hidden_size
        mlp = 3 * self.hidden_size * self.intermediate_size
        norms = 2 * self.hidden_size
        head = 0 if self.tie_embeddings else embed
        return embed + head + self.num_layers * (attn + mlp + norms) + self.hidden_size


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """How this model maps onto the mesh axes of parallel.mesh.AXIS_ORDER."""

    batch_axes: tuple[str, ...] = ("dcn", "data", "fsdp")
    tensor_axis: Optional[str] = "tensor"
    fsdp_axis: Optional[str] = "fsdp"
    seq_axis: Optional[str] = None  # set to "seq" for context parallelism
    #: context-parallel attention scheme: "ring" (ppermute pipeline, any
    #: head count) or "ulysses" (all-to-all head swap; needs heads % seq
    #: degree == 0, runs the fused flash kernel on the full local sequence)
    seq_scheme: str = "ring"
    stage_axis: Optional[str] = None  # set to "stage" for pipeline parallelism
    num_microbatches: Optional[int] = None  # pipeline microbatches (default: #stages)

    def __post_init__(self):
        if self.seq_scheme not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_scheme must be 'ring' or 'ulysses', got "
                f"{self.seq_scheme!r}")

    def act(self, *dims) -> P:
        return P(*dims)


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize params (truncated-normal-free simple scaled normal init)."""
    keys = jax.random.split(rng, 8)
    d, f, l = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    params: Params = {
        "embed": dense(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((l, d), dtype=cfg.dtype),
            "wq": dense(keys[1], (l, d, cfg.q_dim), d),
            "wk": dense(keys[2], (l, d, cfg.kv_dim), d),
            "wv": dense(keys[3], (l, d, cfg.kv_dim), d),
            "wo": dense(keys[4], (l, cfg.q_dim, d), cfg.q_dim),
            "mlp_norm": jnp.ones((l, d), dtype=cfg.dtype),
            "w_gate": dense(keys[5], (l, d, f), d),
            "w_up": dense(keys[6], (l, d, f), d),
            "w_down": dense(keys[7], (l, f, d), f),
        },
        "final_norm": jnp.ones((d,), dtype=cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 99), (d, cfg.vocab_size), d)
    return params


def unstack_params(params: Params) -> Params:
    """Stacked [L, ...] layer weights -> list of per-layer dicts.

    Unstacked layers pair with ``backbone(scan_layers=False)``: each layer's
    weights (and grads, and optimizer moments) are separate buffers, so the
    backward pass writes each dW directly instead of scattering into a
    stacked [L, ...] buffer — profiling showed that scatter (plus the
    matching gather) costing ~10% of the train step at 1B scale.
    """
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        return params
    num = jax.tree.leaves(layers)[0].shape[0]
    out = dict(params)
    out["layers"] = [
        jax.tree.map(lambda w: w[i], layers) for i in range(num)
    ]
    return out


def stack_params(params: Params) -> Params:
    """Inverse of :func:`unstack_params` (e.g. to hand a checkpoint to the
    scan-based decode path)."""
    layers = params["layers"]
    if not isinstance(layers, (list, tuple)):
        return params
    out = dict(params)
    out["layers"] = jax.tree.map(lambda *ws: jnp.stack(ws), *layers)
    return out


def param_specs(cfg: LlamaConfig, policy: ShardingPolicy = ShardingPolicy()) -> Params:
    """PartitionSpec pytree matching :func:`init_params`.

    FSDP shards the contraction (hidden) dim; tensor parallelism shards heads
    / ffn so per-layer matmuls contract locally and only activations need
    collectives — XLA inserts them from these specs.  With a ``stage_axis``
    the stacked layer dim shards over pipeline stages (each stage owns a
    contiguous run of layers — `parallel/pipeline.py`).
    """
    t, fs, st = policy.tensor_axis, policy.fsdp_axis, policy.stage_axis
    specs: Params = {
        "embed": P(t, fs),
        "layers": {
            "attn_norm": P(st, None),
            "wq": P(st, fs, t),
            "wk": P(st, fs, t),
            "wv": P(st, fs, t),
            "wo": P(st, t, fs),
            "mlp_norm": P(st, None),
            "w_gate": P(st, fs, t),
            "w_up": P(st, fs, t),
            "w_down": P(st, t, fs),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(fs, t)
    return specs


def unstack_specs(specs: Params, num_layers: int) -> Params:
    """param_specs for an unstacked tree: drop the leading L dim of each
    layer spec and replicate per layer."""
    def strip(p: P) -> P:
        return P(*tuple(p)[1:])

    per_layer = jax.tree.map(strip, specs["layers"],
                             is_leaf=lambda x: isinstance(x, P))
    out = dict(specs)
    out["layers"] = [per_layer for _ in range(num_layers)]
    return out


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def _constrain(x, mesh: Optional[Mesh], spec: P):
    if mesh is None:
        return x
    # Inside a (partially-)manual shard_map region — e.g. the pipeline body —
    # constraints must be built on the ambient abstract mesh (the concrete
    # mesh's all-Auto axis types no longer match and the backward pass
    # rejects the mismatch); the spec itself only names Auto axes either way.
    cur = jax.sharding.get_abstract_mesh()
    if cur.axis_names:
        mesh = cur
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _embed_lookup(embed, tokens, mesh: Optional[Mesh], policy: ShardingPolicy):
    """Token embedding lookup with an explicit SPMD strategy.

    The embed table is sharded (tensor over vocab x fsdp over model dim);
    left to itself, SPMD lowers the gather by all-gathering table *and*
    indices and then full-rematerializing the output to the activation
    sharding.  Instead: each device masked-gathers its local vocab shard on
    its own (batch, seq) token block and a psum over the vocab axis fills in
    rows owned elsewhere — only activations travel, never the table.
    """
    t = policy.tensor_axis
    if mesh is None or not t or mesh.shape.get(t, 1) <= 1:
        return embed[tokens]
    b, s = tokens.shape
    if (b % _axes_size(mesh, policy.batch_axes)
            or (policy.seq_axis and s % mesh.shape.get(policy.seq_axis, 1))
            or embed.shape[0] % mesh.shape[t]):
        return embed[tokens]  # shape doesn't divide the mesh; let GSPMD pad

    def local(emb, tok):
        vlocal = emb.shape[0]
        ids = tok - lax.axis_index(t) * vlocal
        valid = (ids >= 0) & (ids < vlocal)
        x = emb[jnp.clip(ids, 0, vlocal - 1)]
        return lax.psum(jnp.where(valid[..., None], x, 0), t)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(t, None), P(policy.batch_axes, policy.seq_axis)),
        out_specs=P(policy.batch_axes, policy.seq_axis, None),
        check_vma=False,
    )(embed, tokens)


# Remat modes for the layer scan.  "selective" implements the measured-best
# tradeoff on v5e: save the projection outputs (checkpoint_name "qkv"/"proj"
# below) and rematerialize everything else — norms, RoPE, the flash-attention
# forward, and the wide gate/up MLP intermediates (the MLP recompute costs
# FLOPs but those two [B,S,F] tensors are the bulk of activation memory).
_REMAT_NAMES = ("qkv", "proj")
# With HBM headroom, also saving the attention output and the gated MLP
# product skips their backward recompute (~20% of layer FLOPs) for ~2.5 GB
# at the b8/s1024 1B bench shape — the measured-best single-chip policy.
_REMAT_NAMES_WIDE = ("qkv", "proj", "attn_out", "mlp_mid")


def _layer_remat(layer_fn, remat):
    if remat in (False, "none", None):
        return layer_fn
    if remat == "full":
        return jax.checkpoint(layer_fn)
    if isinstance(remat, (tuple, list)):
        names = tuple(remat)
    elif remat == "wide":
        names = _REMAT_NAMES_WIDE
    elif remat in (True, "selective"):
        names = _REMAT_NAMES
    else:
        raise ValueError(f"remat must be one of False/'none', True/'selective',"
                         f" 'wide', 'full', or a tuple of checkpoint names; "
                         f"got {remat!r}")
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    return jax.checkpoint(layer_fn, policy=policy)


def backbone(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    *,
    mesh: Optional[Mesh] = None,
    policy: ShardingPolicy = ShardingPolicy(),
    positions: Optional[jnp.ndarray] = None,
    remat: bool | str = False,
    scan_layers: bool = True,
) -> jnp.ndarray:
    """Transformer stack up to (and including) the final norm.

    Returns final hidden states [B, S, D] in model dtype.  ``remat`` is one
    of False/"none", True/"selective", "full" (see :data:`_REMAT_NAMES`).
    ``scan_layers=False`` unrolls the layer loop (faster on-chip for
    small/medium depth, O(L) compile time — see the inline note).
    """
    b, s = tokens.shape
    inv_freqs = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))

    # context parallelism active (either scheme: ring or ulysses)
    use_seq = policy.seq_axis is not None and mesh is not None and \
        mesh.shape.get(policy.seq_axis, 1) > 1
    use_pipeline = policy.stage_axis is not None and mesh is not None and \
        mesh.shape.get(policy.stage_axis, 1) > 1
    if use_pipeline and use_seq:
        # both context-parallel schemes are full-manual shard_maps; nesting
        # one inside the pipeline's partial-manual region is untested —
        # shard long context with seq OR pipeline the depth, not both (yet).
        raise NotImplementedError(
            "pipeline (stage) and context (seq) parallelism can't be "
            "combined yet; drop one of the two axes from the mesh/policy")
    if use_pipeline and positions is not None:
        # the layer body closes over full-batch positions; microbatch
        # splitting inside the schedule doesn't slice them
        raise NotImplementedError(
            "custom `positions` are not supported on the pipeline path yet; "
            "pass positions=None with stage parallelism")
    if use_seq and positions is not None:
        # both schemes derive each shard's mask from global 0..S-1
        # positions; custom (packed/offset) positions would silently
        # diverge from the RoPE phases.
        raise NotImplementedError(
            "custom `positions` are not supported on the context-parallel "
            "(seq) path yet; pass positions=None with seq parallelism"
        )
    default_positions = positions is None
    if default_positions:
        # [1, S] broadcasts everywhere it's used; a [B, S] repeat would be
        # resharded (and was the source of SPMD full-remat warnings under
        # sequence sharding).
        positions = jnp.arange(s)[None, :]

    # The fused kernel handles the standard contiguous-causal training path;
    # under a mesh it runs per-device via shard_map, so the head axis must
    # divide both query and KV heads.
    use_flash = (
        not use_seq
        and default_positions
        and flash.supports(s, cfg.head_dim, cfg.dtype,
                           group=cfg.num_heads // cfg.num_kv_heads)
    )
    if use_flash and mesh is not None:
        t = policy.tensor_axis
        tsize = mesh.shape.get(t, 1) if t else 1
        if tsize > 1 and (cfg.num_kv_heads % tsize or cfg.num_heads % tsize):
            use_flash = False
        # shard_map needs the (micro)batch to divide the batch mesh axes —
        # under the pipeline the layer body sees b / num_microbatches rows
        eff_b = b
        if use_pipeline:
            m = policy.num_microbatches or mesh.shape[policy.stage_axis]
            if b % m:
                use_flash = False
            else:
                eff_b = b // m
        if eff_b % _axes_size(mesh, policy.batch_axes):
            use_flash = False

    act_spec = P(policy.batch_axes, policy.seq_axis, None)

    x = _embed_lookup(params["embed"].astype(cfg.dtype), tokens, mesh, policy)
    x = _constrain(x, mesh, act_spec)

    def attn_fn(q, k, v):
        if use_seq:
            if policy.seq_scheme == "ulysses":
                from dstack_tpu.ops.ulysses import (
                    supports as ulysses_supports,
                    ulysses_attention_sharded,
                )

                nt = mesh.shape.get(policy.tensor_axis, 1) \
                    if policy.tensor_axis else 1
                if not ulysses_supports(
                        cfg, mesh.shape[policy.seq_axis], nt):
                    raise ValueError(
                        f"seq_scheme='ulysses' needs num_heads "
                        f"({cfg.num_heads}) and num_kv_heads "
                        f"({cfg.num_kv_heads}) divisible by seq x tensor "
                        f"degree; use seq_scheme='ring' instead")
                return ulysses_attention_sharded(
                    mesh, q, k, v,
                    seq_axis=policy.seq_axis,
                    batch_axes=policy.batch_axes,
                    head_axis=policy.tensor_axis,
                )
            return ring_attention_sharded(
                mesh, q, k, v,
                seq_axis=policy.seq_axis,
                batch_axes=policy.batch_axes,
                head_axis=policy.tensor_axis,
            )
        if use_flash:
            if mesh is None:
                return flash.flash_attention(q, k, v)
            return flash.flash_attention_sharded(
                mesh, q, k, v,
                batch_axes=policy.batch_axes, head_axis=policy.tensor_axis,
            )
        return causal_attention(q, k, v, q_positions=positions, kv_positions=positions)

    def attention_block(h, lp):
        # (a head-major [B,H,S,D] kernel boundary was tried here — the
        # saved transposes were outweighed by slower dhk-projection einsums
        # on v5e, so the layout stays [B,S,H,D]).  Batch size comes from h,
        # not the closure: under pipeline parallelism the layer body runs on
        # microbatches of b/num_microbatches.
        bb = h.shape[0]
        q = checkpoint_name(jnp.einsum("bsd,dq->bsq", h, lp["wq"]), "qkv") \
            .reshape(bb, s, cfg.num_heads, cfg.head_dim)
        k = checkpoint_name(jnp.einsum("bsd,dq->bsq", h, lp["wk"]), "qkv") \
            .reshape(bb, s, cfg.num_kv_heads, cfg.head_dim)
        v = checkpoint_name(jnp.einsum("bsd,dq->bsq", h, lp["wv"]), "qkv") \
            .reshape(bb, s, cfg.num_kv_heads, cfg.head_dim)
        q = apply_rope(q, positions, inv_freqs)
        k = apply_rope(k, positions, inv_freqs)
        attn = checkpoint_name(
            attn_fn(q, k, v).reshape(bb, s, cfg.q_dim), "attn_out")
        return jnp.einsum("bsq,qd->bsd", attn, lp["wo"])

    def layer(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        x = x + checkpoint_name(attention_block(h, lp), "proj")
        x = _constrain(x, mesh, act_spec)
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        gated = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]))
        up = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
        mid = checkpoint_name(gated * up, "mlp_mid")
        x = x + checkpoint_name(
            jnp.einsum("bsf,fd->bsd", mid, lp["w_down"]), "proj")
        x = _constrain(x, mesh, act_spec)
        return x, None

    layer_fn = _layer_remat(layer, remat)
    layers = params["layers"]
    if use_pipeline:
        if isinstance(layers, (list, tuple)):
            raise NotImplementedError(
                "pipeline parallelism needs stacked [L, ...] layer weights "
                "(the stage axis shards the layer dim); don't unstack")
        from dstack_tpu.parallel.pipeline import pipeline_layers

        x = pipeline_layers(
            layer_fn, layers, x,
            mesh=mesh, stage_axis=policy.stage_axis,
            num_microbatches=policy.num_microbatches,
        )
    elif isinstance(layers, (list, tuple)):
        # unstacked per-layer weights (see unstack_params): plain loop,
        # every dW its own buffer
        for lp in layers:
            x, _ = layer_fn(x, lp)
    elif scan_layers:
        x, _ = lax.scan(lambda c, lp: layer_fn(c, lp), x, layers)
    else:
        # Unrolled layers over stacked weights: profiling the scan path on
        # v5e showed ~30% of the step in dynamic-update-slice/copy fusions
        # (stacked saved residuals + stacked grad accumulation inside the
        # while loop) while matmuls already ran at ~peak.  Unrolling trades
        # O(L) compile time for zero stacking traffic.  (Grad scatter into
        # the stacked weights remains — unstack_params removes that too.)
        for l in range(cfg.num_layers):
            lp = jax.tree.map(lambda w: w[l], layers)
            x, _ = layer_fn(x, lp)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def output_head(params: Params, cfg: LlamaConfig):
    """[D, V] output projection.  An explicit "lm_head" entry always wins
    (untied models; also the serving engine's int8 copy of a tied head —
    serving/quant.py); tied models without one use the embedding
    transpose."""
    if "lm_head" in params:
        return params["lm_head"]
    return params["embed"].T


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    *,
    mesh: Optional[Mesh] = None,
    policy: ShardingPolicy = ShardingPolicy(),
    positions: Optional[jnp.ndarray] = None,
    remat: bool | str = False,
) -> jnp.ndarray:
    """Full-sequence forward; returns float32 logits [B, S, V].

    Training should prefer :func:`backbone` +
    :func:`dstack_tpu.ops.loss.chunked_cross_entropy`, which never
    materializes this [B, S, V] tensor.
    """
    x = backbone(params, tokens, cfg, mesh=mesh, policy=policy,
                 positions=positions, remat=remat)
    logits = jnp.einsum("bsd,dv->bsv", x, output_head(params, cfg),
                        preferred_element_type=jnp.float32)
    return _constrain(logits, mesh, P(policy.batch_axes, policy.seq_axis, policy.tensor_axis))


# ---------------------------------------------------------------------------
# Decode (serving) path
# ---------------------------------------------------------------------------


def init_kv_caches(cfg: LlamaConfig, batch: int, max_len: int) -> KVCache:
    """Stacked [L, B, S, Hkv, D] cache pytree for scan-based decode."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype=cfg.dtype),
        v=jnp.zeros(shape, dtype=cfg.dtype),
        length=jnp.zeros((), dtype=jnp.int32),
    )


def decode_step(
    params: Params,
    token: jnp.ndarray,  # [B] int32 — current token
    cache: KVCache,
    cfg: LlamaConfig,
) -> tuple[jnp.ndarray, KVCache]:
    """One autoregressive step; returns (logits [B, V], updated cache)."""
    b = token.shape[0]
    pos = cache.length
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    inv_freqs = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))

    x = params["embed"].astype(cfg.dtype)[token][:, None, :]  # [B, 1, D]

    def layer(carry, inputs):
        x = carry
        lp, layer_k, layer_v = inputs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = jnp.einsum("bsd,dq->bsq", h, lp["wq"]).reshape(b, 1, cfg.num_heads, cfg.head_dim)
        k = jnp.einsum("bsd,dq->bsq", h, lp["wk"]).reshape(b, 1, cfg.num_kv_heads, cfg.head_dim)
        v = jnp.einsum("bsd,dq->bsq", h, lp["wv"]).reshape(b, 1, cfg.num_kv_heads, cfg.head_dim)
        q = apply_rope(q, positions, inv_freqs)
        k = apply_rope(k, positions, inv_freqs)
        attn, new_cache = decode_step_attention(
            q, KVCache(k=layer_k, v=layer_v, length=pos), k, v
        )
        x = x + jnp.einsum("bsq,qd->bsd", attn.reshape(b, 1, cfg.q_dim), lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        gated = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]))
        up = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
        x = x + jnp.einsum("bsf,fd->bsd", gated * up, lp["w_down"])
        return x, (new_cache.k, new_cache.v)

    x, (new_k, new_v) = lax.scan(layer, x, (params["layers"], cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = output_head(params, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, head, preferred_element_type=jnp.float32)
    return logits[:, 0, :], KVCache(k=new_k, v=new_v, length=pos + 1)
