"""A hybrid decoder of the Ling-3.0-flash kind: KDA linear-attention layers
with an MLA layer every few, and sparse experts with sigmoid routing.

Pre-norm residual blocks, ``x += mixer(norm(x)); x += ffn(norm(x))``, with

* mixer ``kda``: Kimi Delta Attention (``ops/kda.py``) behind a causal
  depthwise convolution over the last ``conv_kernel`` tokens of q, k and v;
  its memory is a fixed [H, d_k, d_v] float32 state and the convolution's
  tail, whatever the length;
* mixer ``mla``: multi-head latent attention (``ops/mla.py``); its memory is
  one latent row a token;
* ffn: a dense SwiGLU in the first ``first_k_dense`` layers, then routed
  experts: sigmoid scores over ALL ``num_experts``, group-limited top-k
  chosen on ``score + bias``, the chosen scores normalised and scaled, plus
  a shared expert.  This chip holds ``experts_held`` of the experts, from
  ``expert_offset``: the layer routes over all, computes the pairs that fall
  on its own experts (sorted by expert, one grouped product a matrix, no
  capacity and no dropped pair) and adds nothing for the absent ones — the
  chip's share of an expert-parallel deployment, without its exchange.

Layers differ in kind, so the weights are a LIST of per-layer dicts and the
stack is a Python loop, not a scan.  The functions here work on one
sequence [T, D] (prefill, a chunk of it) or on one token of every slot
[B, D] (a decode step); the engine's programs (``serving/hybrid.py``) hold
the state and the paged pool around them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from dstack_tpu.models import experts
from dstack_tpu.models.experts import (  # noqa: F401  (LOAD_FIELDS: re-export)
    LOAD_FIELDS,
    expert_load,
    held_experts,
    swiglu as _swiglu,
)
from dstack_tpu.ops import kda, mla
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.ops.rotary import apply_rope, rope_frequencies

Params = dict[str, Any]
LANE = 128


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157_184
    hidden_size: int = 2560
    intermediate_size: int = 6144          # the leading dense layers' MLP
    num_layers: int = 42
    layer_types: Sequence[str] = ()        # "kda" | "mla", one a layer
    first_k_dense: int = 2
    num_heads: int = 32
    head_dim: int = 128                    # KDA's d_k = d_v
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6_000_000.0
    num_experts: int = 512
    experts_held: int = 512
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 768
    rms_eps: float = 1e-6
    max_seq_len: int = 131_072
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            "mla" if (i + 1) % 6 == 0 else "kda"
            for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", types)
        if len(types) != self.num_layers or set(types) - {"kda", "mla"}:
            raise ValueError(f"layer_types {types} does not name a mixer "
                             f"(kda | mla) for each of {self.num_layers}")
        if self.num_experts % self.n_group:
            raise ValueError("num_experts must divide into n_group groups")
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError("the held experts pass the router's last one")
        if self.tie_embeddings:
            raise ValueError("the output head is its own matrix")

    @classmethod
    def tiny(cls, **kw) -> "LingHybridConfig":
        """Test size: every mechanism, no published width."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=4, layer_types=("kda", "kda", "mla", "kda"),
            first_k_dense=1, num_heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=10_000.0, num_experts=16, experts_held=16,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            max_seq_len=512, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)

    # -- sizes ---------------------------------------------------------------
    @property
    def kda_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kda_layers(self) -> int:
        return self.layer_types.count("kda")

    @property
    def mla_layers(self) -> int:
        return self.layer_types.count("mla")

    @property
    def latent_dim(self) -> int:
        """Numbers a token holds in an MLA layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """The pool row's width: ``latent_dim`` up to whole 128-lane tiles
        (the TPU keeps a narrower row blocks-minor and converts the pool)."""
        return -(-self.latent_dim // LANE) * LANE

    def mixer_params(self, kind: str) -> int:
        d, h = self.hidden_size, self.num_heads
        if kind == "kda":
            c = self.kda_dim
            return (3 * d * c + self.conv_kernel * 3 * c   # q k v, conv
                    + d * c + h + c                         # gate, A_log, dt
                    + 2 * d * h + self.head_dim + c * d)    # beta, g, norm, o
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        r = self.kv_lora_rank
        return (d * h * qk + d * self.latent_dim + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    def ffn_params(self, layer: int) -> int:
        d = self.hidden_size
        if layer < self.first_k_dense:
            return 3 * d * self.intermediate_size
        return (d * self.num_experts + self.num_experts
                + self.experts_held * 3 * d * self.moe_intermediate_size
                + 3 * d * self.shared_expert_intermediate_size)

    def num_params(self) -> int:
        """Parameters held HERE (``experts_held`` of the experts)."""
        d = self.hidden_size
        layers = sum(self.mixer_params(kind) + self.ffn_params(i) + 2 * d
                     for i, kind in enumerate(self.layer_types))
        return 2 * self.vocab_size * d + layers + d

    def recurrent_state_bytes(self, slots: int) -> int:
        tail = (self.conv_kernel - 1) * 3 * self.kda_dim
        return self.kda_layers * slots * (
            4 * self.num_heads * self.head_dim * self.head_dim
            + tail * jnp.dtype(self.dtype).itemsize)


def init_params(rng: jax.Array, cfg: LingHybridConfig) -> Params:
    """Random weights in the tree layout the engine's ``params=`` takes."""
    d, h, dt = cfg.hidden_size, cfg.num_heads, cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def layer(key, i, kind):
        k = jax.random.split(key, 16)
        lp = {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt)}
        if kind == "kda":
            c = cfg.kda_dim
            lp.update(
                w_qkv=dense(k[0], (d, 3 * c), d),
                conv_w=dense(k[1], (cfg.conv_kernel, 3 * c), cfg.conv_kernel),
                w_f=dense(k[2], (d, c), d),
                a_log=jnp.zeros((h,), jnp.float32),
                dt_bias=jnp.zeros((c,), jnp.float32),
                w_beta=dense(k[3], (d, h), d), w_g=dense(k[4], (d, h), d),
                o_norm=jnp.ones((cfg.head_dim,), dt),
                wo=dense(k[5], (c, d), c))
        else:
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            r = cfg.kv_lora_rank
            lp.update(
                wq=dense(k[0], (d, h * qk), d),
                w_dkv=dense(k[1], (d, cfg.latent_dim), d),
                kv_norm=jnp.ones((r,), dt),
                w_ukv=dense(k[2], (r, h * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), r),
                wo=dense(k[3], (h * cfg.v_head_dim, d), h * cfg.v_head_dim))
        if i < cfg.first_k_dense:
            f = cfg.intermediate_size
            lp.update(w_gate=dense(k[6], (d, f), d),
                      w_up=dense(k[7], (d, f), d),
                      w_down=dense(k[8], (f, d), f))
        else:
            e, f = cfg.experts_held, cfg.moe_intermediate_size
            fs = cfg.shared_expert_intermediate_size
            lp.update(
                router=dense(k[6], (d, cfg.num_experts), d),
                router_bias=jnp.zeros((cfg.num_experts,), jnp.float32),
                we_gate=dense(k[7], (e, d, f), d),
                we_up=dense(k[8], (e, d, f), d),
                we_down=dense(k[9], (e, f, d), f),
                ws_gate=dense(k[10], (d, fs), d),
                ws_up=dense(k[11], (d, fs), d),
                ws_down=dense(k[12], (fs, d), fs))
        return lp

    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    keys = jax.random.split(k_layers, cfg.num_layers)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
        "final_norm": jnp.ones((d,), dt),
        "layers": [layer(keys[i], i, kind)
                   for i, kind in enumerate(cfg.layer_types)],
    }


# -- feed-forward --------------------------------------------------------------

def route(h, lp, cfg: LingHybridConfig):
    """Experts and weights of every token of ``h`` [T, D]: ``(ids [T, k],
    weights [T, k] float32)`` over ALL ``num_experts``, chosen inside the
    ``topk_group`` best of ``n_group`` groups (``experts.route``)."""
    return experts.route(
        h, lp["router"], lp["router_bias"], top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
        topk_group=cfg.topk_group)


def moe_ffn(h, lp, cfg: LingHybridConfig, token_mask=None):
    """Routed experts held here + the shared expert, for ``h`` [T, D].
    Returns ``(y, load)``; ``load`` = float32 [held pairs, absent pairs,
    largest count of one held expert, mean count of a held expert, held
    experts with a pair] over the unmasked tokens."""
    ids, weights = route(h, lp, cfg)
    y, counts = held_experts(h, ids, weights, lp, cfg, token_mask)
    with jax.named_scope("shared_expert"):
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    tokens = (h.shape[0] if token_mask is None
              else token_mask.sum().astype(jnp.float32))
    load = expert_load(counts, tokens, cfg)
    return y, load


def _ffn(x, lp, cfg, token_mask):
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return x + _swiglu(h, lp["w_gate"], lp["w_up"],
                               lp["w_down"]), None
    y, load = moe_ffn(h, lp, cfg, token_mask)
    return x + y, load


# -- mixers --------------------------------------------------------------------

def _kda_inputs(h, qkv, lp, cfg: LingHybridConfig, live):
    """From the convolved projections to the recurrence's operands; rows
    where ``live`` is false get ``g = 0, beta = 0`` and leave the state."""
    n, hd = cfg.num_heads, cfg.head_dim
    lead = qkv.shape[:-1]
    q, k, v = (a.reshape(lead + (n, hd))
               for a in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
    q = kda.l2_normalize(q) * hd ** -0.5
    k = kda.l2_normalize(k)
    g = kda.kda_gate((h @ lp["w_f"]).reshape(lead + (n, hd)), lp["a_log"],
                     lp["dt_bias"], cfg.kda_lower_bound)
    beta = jax.nn.sigmoid((h @ lp["w_beta"]).astype(jnp.float32))
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    return q, k, v, g, beta


def _kda_output(o, h, lp, cfg: LingHybridConfig):
    """Per-head RMSNorm, one sigmoid gate a head, the output projection."""
    gate = jax.nn.sigmoid((h @ lp["w_g"]).astype(jnp.float32))
    o = rms_norm(o, lp["o_norm"], cfg.rms_eps) * gate[..., None]
    return o.astype(h.dtype).reshape(h.shape[:-1] + (cfg.kda_dim,)) @ lp["wo"]


def kda_sequence(x, lp, cfg: LingHybridConfig, length, state, tail):
    """The KDA mixer over one sequence ``x`` [T, D] of which ``length``
    tokens are real, from ``state`` [H, d_k, d_v] and the convolution's
    ``tail`` [kernel - 1, 3 * kda_dim] (the projections of the tokens just
    before).  Returns ``(y, state, tail)`` after the last real token."""
    t = x.shape[0]
    reach = cfg.conv_kernel - 1
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    proj = h @ lp["w_qkv"]
    padded = jnp.concatenate([tail.astype(proj.dtype), proj], axis=0)
    qkv = sum(lp["conv_w"][j] * padded[j:j + t]
              for j in range(cfg.conv_kernel))
    # token i sits at row i + reach: the last `reach` real tokens' rows
    tail = jax.lax.dynamic_slice_in_dim(padded, length, reach, axis=0)
    live = jnp.arange(t) < length
    q, k, v, g, beta = _kda_inputs(h, qkv, lp, cfg, live)
    o, state = kda.kda_chunked(state, q, k, v, g, beta)
    return _kda_output(o, h, lp, cfg), state, tail


def _on_chip() -> bool:
    """Whether the decode step's recurrence is the Pallas kernel
    (``ops/kda.py`` ``kda_step_into``): every backend but the CPU's, as for
    the grouped product (``models/experts.py``); the CPU backend keeps
    :func:`kda.kda_step`, the form the tests hold the kernel to."""
    return experts._on_chip()


def kda_token(x, lp, cfg: LingHybridConfig, live, states, layer: int, tail):
    """The KDA mixer for one token of every slot: ``x`` [B, D], ``states``
    the stack [L_kda, B, H, d_k, d_v] of which this is KDA layer ``layer``,
    ``tail`` [B, kernel - 1, 3 * kda_dim].  Returns ``(y, states, tail)``
    with layer ``layer`` of the stack updated (in place, on the chip).
    Slots that are not ``live`` keep state and tail."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    proj = h @ lp["w_qkv"]
    window = jnp.concatenate([tail.astype(proj.dtype), proj[:, None]], axis=1)
    qkv = jnp.einsum("bjc,jc->bc", window, lp["conv_w"])
    tail = jnp.where(live[:, None, None], window[:, 1:], tail)
    q, k, v, g, beta = _kda_inputs(h, qkv, lp, cfg, live)
    if _on_chip():
        o, states = kda.kda_step_into(states, layer, q, k, v, g, beta)
    else:
        o, s = kda.kda_step(states[layer], q, k, v, g, beta)
        states = states.at[layer].set(s)
    return _kda_output(o, h, lp, cfg), states, tail


def _mla_project(x, lp, cfg: LingHybridConfig, positions):
    """Queries and the new latent rows of ``x`` [N, D] at ``positions``
    [N]: ``(q_nope [N, H, d_n], q_rope [N, H, d_r], rows [N, lanes])``."""
    n, h = x.shape[0], cfg.num_heads
    d_n, d_r, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    inv = jnp.asarray(rope_frequencies(d_r, cfg.rope_theta))
    hid = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = (hid @ lp["wq"]).reshape(n, h, d_n + d_r)
    q_rope = apply_rope(q[..., d_n:], positions, inv)
    ckr = hid @ lp["w_dkv"]
    c = rms_norm(ckr[:, :r], lp["kv_norm"], cfg.rms_eps)
    k_rope = apply_rope(ckr[:, None, r:], positions, inv)[:, 0]
    return q[..., :d_n], q_rope, mla.latent_rows(c, k_rope, cfg.latent_lanes)


def _w_ukv(lp, cfg: LingHybridConfig):
    return lp["w_ukv"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)


# -- the stack -----------------------------------------------------------------

def sequence_forward(params: Params, cfg: LingHybridConfig, tokens, length,
                     start, states, tails, attend: Callable):
    """One sequence (a whole prompt, or one chunk of it) through the stack.

    ``tokens`` [T] of which ``length`` are real, the first at position
    ``start``; ``states`` [L_kda, H, d_k, d_v] and ``tails`` [L_kda, kernel
    - 1, 3 * kda_dim] are this sequence's recurrent state before the first
    token.  ``attend(m, rows)`` stores the new latent ``rows`` [T, lanes] of
    MLA layer ``m`` and returns ``(kv_rows [S, lanes], kv_pos [S])``, what
    the layer's queries may see (positions past a query's own are masked).
    Returns ``(logits [V] float32 at the last real token, states, tails)``.
    """
    t = tokens.shape[0]
    positions = start + jnp.arange(t)
    live = jnp.arange(t) < length
    x = params["embed"].astype(cfg.dtype)[tokens]
    new_states, new_tails = [], []
    for i, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        if kind == "kda":
            j = len(new_states)
            y, s, tl = kda_sequence(x, lp, cfg, length, states[j], tails[j])
            new_states.append(s)
            new_tails.append(tl)
        else:
            q_nope, q_rope, rows = _mla_project(x, lp, cfg, positions)
            kv_rows, kv_pos = attend(i - len(new_states), rows)
            o = mla.expanded(q_nope, q_rope, kv_rows, _w_ukv(lp, cfg),
                             positions, kv_pos)
            y = o.reshape(t, -1) @ lp["wo"]
        x, _ = _ffn(x + y, lp, cfg, live)
    with jax.named_scope("lm_head"):
        last = rms_norm(x[length - 1], params["final_norm"], cfg.rms_eps)
        logits = jnp.matmul(last, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, jnp.stack(new_states), jnp.stack(new_tails)


def decode_step(params: Params, cfg: LingHybridConfig, x, positions, live,
                states, tails, attend: Callable):
    """One token of every slot through the stack: ``x`` [B, D] at
    ``positions`` [B]; ``states`` [L_kda, B, H, d_k, d_v], ``tails`` [L_kda,
    B, kernel - 1, 3 * kda_dim].  ``attend(m, q_nope, q_rope, rows, w_ukv)``
    is MLA layer ``m`` over whatever the caller keeps of the cache, after it
    has taken this step's ``rows`` [B, lanes].  Returns ``(hidden [B, D],
    states, tails, load)``; ``load`` sums :func:`moe_ffn`'s over the expert
    layers."""
    load = jnp.zeros((LOAD_FIELDS,), jnp.float32)
    j = 0
    for i, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        if kind == "kda":
            y, states, tl = kda_token(x, lp, cfg, live, states, j, tails[j])
            tails = tails.at[j].set(tl)
            j += 1
        else:
            q_nope, q_rope, rows = _mla_project(x, lp, cfg, positions)
            o = attend(i - j, q_nope, q_rope, rows, _w_ukv(lp, cfg))
            y = o.reshape(x.shape[0], -1) @ lp["wo"]
        x, layer_load = _ffn(x + y, lp, cfg, live)
        if layer_load is not None:
            load = load + layer_load
    return x, states, tails, load
