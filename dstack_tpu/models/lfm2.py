"""A mixed decoder of the LFM2-MoE kind (``model_type: lfm2_moe``): gated
short convolutions with a grouped-query attention layer every few, and
sparse experts with sigmoid routing.

Pre-norm residual blocks, ``x += mixer(norm(x)); x += ffn(norm(x))``, with

* mixer ``conv``: ``[B, C, X] = W_in u``; a causal depthwise convolution
  over the last ``conv_L_cache`` rows of ``B * X`` (no activation, zeros
  before the start); ``y = W_out (C * z)``.  Its memory is the last
  ``conv_L_cache - 1`` rows of ``B * X``, whatever the length;
* mixer ``full_attention``: GQA with a per-head RMSNorm on q and on k, then
  rotate-half RoPE; its memory is a K and a V row a token;
* ffn: a dense SwiGLU in the first ``num_dense_layers`` layers, then routed
  experts: sigmoid scores over all ``num_experts``, the top
  ``num_experts_per_tok`` chosen on ``score + expert_bias``, the chosen
  scores normalised (``+ 1e-6`` in the sum) and scaled; no groups, no
  shared expert.  This chip holds ``experts_held`` of the experts, from
  ``expert_offset`` (all, by default), through the sorted grouped product of
  ``models/experts.py``.

Layers differ in kind, so the weights are a LIST of per-layer dicts and the
stack is a Python loop (as ``models/ling_hybrid.py``).  The functions work
on a group of sequences [G, T] (whole prompts, or chunks of prompts of
different slots) or on one token of every slot [B, D] (a decode step);
what the attention layers see of the cache is the caller's (``attend``):
the engine's programs (``serving/mixed.py``) hold the K/V pool and the
convolution tails around them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from dstack_tpu.models import experts
from dstack_tpu.models.experts import (  # noqa: F401  (LOAD_FIELDS: re-export)
    LOAD_FIELDS,
    expert_load,
    held_experts,
    swiglu,
)
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.ops.rotary import apply_rope, rope_frequencies

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys (LFM2-24B-A2B's values), and what
    this chip holds of the experts."""
    vocab_size: int = 65_536
    hidden_size: int = 2048
    intermediate_size: int = 11_776        # the leading dense layers' MLP
    num_hidden_layers: int = 40
    layer_types: Sequence[str] = ()        # "conv" | "full_attention"
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None         # hidden_size / heads
    conv_L_cache: int = 3
    conv_bias: bool = False
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    experts_held: Optional[int] = None     # all
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    max_position_embeddings: int = 128_000
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            "full_attention" if i % 4 == 2 else "conv"
            for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)
        if (len(types) != self.num_hidden_layers
                or set(types) - {"conv", "full_attention"}):
            raise ValueError(
                f"layer_types {types} does not name a mixer (conv | "
                f"full_attention) for each of {self.num_hidden_layers}")
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        if self.experts_held is None:
            object.__setattr__(self, "experts_held",
                               self.num_experts - self.expert_offset)
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError("the held experts pass the router's last one")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must group evenly under kv heads")
        # published keys whose other value no code here computes
        for key, served in (("conv_bias", False), ("norm_topk_prob", True),
                            ("use_expert_bias", True),
                            ("tie_word_embeddings", True)):
            if getattr(self, key) is not served:
                raise ValueError(f"{key} is served as {served} only (the "
                                 "published value)")

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        """Test size: every mechanism, no published width."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=5,
            layer_types=("conv", "full_attention", "conv", "conv",
                         "full_attention"),
            num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            rope_theta=10_000.0, max_position_embeddings=512,
            dtype=jnp.float32)
        base.update(kw)
        return cls(**base)

    @classmethod
    def lfm2_24b_a2b_9l(cls, **kw) -> "Lfm2MoeConfig":
        """Published layers 1-9 of LFM2-24B-A2B at every published width,
        all 64 experts: one of five pipeline stages
        (``benchmarks/configs/lfm2-24b-a2b-9l.json``)."""
        base = dict(num_hidden_layers=9, layer_types=cls().layer_types[1:10],
                    num_dense_layers=1)
        base.update(kw)
        return cls(**base)

    # -- sizes ---------------------------------------------------------------
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count("conv")

    @property
    def attention_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def conv_reach(self) -> int:
        """Rows of ``B * X`` a convolution keeps of the tokens before."""
        return self.conv_L_cache - 1

    @property
    def kv_lanes(self) -> int:
        """A pool row's width: the kv heads folded into the lanes."""
        return self.num_key_value_heads * self.head_dim

    def mixer_params(self, kind: str) -> int:
        d = self.hidden_size
        if kind == "conv":
            return 3 * d * d + self.conv_L_cache * d + d * d
        q = self.num_attention_heads * self.head_dim
        return 2 * d * q + 2 * d * self.kv_lanes + 2 * self.head_dim

    def ffn_params(self, layer: int) -> int:
        d = self.hidden_size
        if layer < self.num_dense_layers:
            return 3 * d * self.intermediate_size
        return (d * self.num_experts + self.num_experts
                + self.experts_held * 3 * d * self.moe_intermediate_size)

    def num_params(self) -> int:
        """Parameters held HERE (``experts_held`` of the experts; the
        embedding once, it is the head too)."""
        d = self.hidden_size
        layers = sum(self.mixer_params(kind) + self.ffn_params(i) + 2 * d
                     for i, kind in enumerate(self.layer_types))
        return self.vocab_size * d + layers + d

    def recurrent_state_bytes(self, slots: int) -> int:
        return (self.conv_layers * slots * self.conv_reach
                * self.hidden_size * jnp.dtype(self.dtype).itemsize)


def init_params(rng: jax.Array, cfg: Lfm2MoeConfig) -> Params:
    """Random weights in the tree layout the engine's ``params=`` takes."""
    d, dt = cfg.hidden_size, cfg.dtype
    q = cfg.num_attention_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def layer(key, i, kind):
        k = jax.random.split(key, 12)
        lp = {"operator_norm": jnp.ones((d,), dt),
              "ffn_norm": jnp.ones((d,), dt)}
        if kind == "conv":
            lp.update(
                w_in=dense(k[0], (d, 3 * d), d),
                conv_w=dense(k[1], (cfg.conv_L_cache, d), cfg.conv_L_cache),
                w_out=dense(k[2], (d, d), d))
        else:
            lp.update(
                wq=dense(k[0], (d, q), d),
                wk=dense(k[1], (d, cfg.kv_lanes), d),
                wv=dense(k[2], (d, cfg.kv_lanes), d),
                wo=dense(k[3], (q, d), q),
                q_norm=jnp.ones((cfg.head_dim,), dt),
                k_norm=jnp.ones((cfg.head_dim,), dt))
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            lp.update(w_gate=dense(k[6], (d, f), d),
                      w_up=dense(k[7], (d, f), d),
                      w_down=dense(k[8], (f, d), f))
        else:
            e, f = cfg.experts_held, cfg.moe_intermediate_size
            lp.update(
                router=dense(k[6], (d, cfg.num_experts), d),
                router_bias=jnp.zeros((cfg.num_experts,), jnp.float32),
                we_gate=dense(k[7], (e, d, f), d),
                we_up=dense(k[8], (e, d, f), d),
                we_down=dense(k[9], (e, f, d), f))
        return lp

    k_embed, k_layers = jax.random.split(rng)
    keys = jax.random.split(k_layers, cfg.num_hidden_layers)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "embedding_norm": jnp.ones((d,), dt),
        "layers": [layer(keys[i], i, kind)
                   for i, kind in enumerate(cfg.layer_types)],
    }


# -- feed-forward -------------------------------------------------------------

def route(h, lp, cfg: Lfm2MoeConfig):
    """Experts and weights of every token of ``h`` [T, D]: ``(ids [T, k],
    weights [T, k] float32)`` over all ``num_experts``; the selection bias
    picks, the plain scores weigh (``experts.route``; ``1e-6`` in the
    weights' sum)."""
    return experts.route(
        h, lp["router"], lp["router_bias"], top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, eps=1e-6)


def moe_ffn(h, lp, cfg: Lfm2MoeConfig, token_mask=None):
    """The routed experts held here, for ``h`` [T, D]: ``(y, load)``;
    ``load`` is :func:`experts.expert_load`'s vector over the unmasked
    tokens."""
    ids, weights = route(h, lp, cfg)
    y, counts = held_experts(h, ids, weights, lp, cfg, token_mask)
    tokens = (h.shape[0] if token_mask is None
              else token_mask.sum().astype(jnp.float32))
    return y, expert_load(counts, tokens, cfg)


def _ffn(x, lp, cfg, token_mask):
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    y, load = moe_ffn(h, lp, cfg, token_mask)
    return x + y, load


# -- mixers -------------------------------------------------------------------

def _gates(x, lp, cfg: Lfm2MoeConfig):
    """``(B * X, C)`` of ``x`` [..., D], each [..., D]: the convolution's
    input in the served type (the form its tail is kept in) and the output
    gate."""
    h = rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    b, c, xx = jnp.split(h @ lp["w_in"], 3, axis=-1)
    return (b.astype(jnp.float32) * xx.astype(jnp.float32)).astype(x.dtype), c


def _gated_out(c, z, lp):
    return (c.astype(jnp.float32) * z).astype(c.dtype) @ lp["w_out"]


@jax.named_scope("short_conv")
def conv_sequence(x, lp, cfg: Lfm2MoeConfig, length, tail):
    """The convolution mixer over one sequence ``x`` [T, D] of which
    ``length`` tokens are real, behind ``tail`` [conv_L_cache - 1, D] (the
    ``B * X`` rows of the tokens just before; zeros at a prompt's start).
    Returns ``(y, tail)`` after the last real token."""
    t = x.shape[0]
    bx, c = _gates(x, lp, cfg)
    padded = jnp.concatenate([tail.astype(bx.dtype), bx], axis=0)
    taps = lp["conv_w"].astype(jnp.float32)
    # token i sits at row i + reach and sees itself under the LAST tap
    z = sum(taps[j] * padded[j:j + t] for j in range(cfg.conv_L_cache))
    tail = jax.lax.dynamic_slice_in_dim(padded, length, cfg.conv_reach,
                                        axis=0)
    return _gated_out(c, z, lp), tail


@jax.named_scope("short_conv")
def conv_token(x, lp, cfg: Lfm2MoeConfig, live, tail):
    """The convolution mixer for one token of every slot: ``x`` [B, D],
    ``tail`` [B, conv_L_cache - 1, D].  Slots that are not ``live`` keep
    their tail."""
    bx, c = _gates(x, lp, cfg)
    window = jnp.concatenate([tail.astype(bx.dtype), bx[:, None]], axis=1)
    z = jnp.einsum("bjc,jc->bc", window.astype(jnp.float32),
                   lp["conv_w"].astype(jnp.float32))
    tail = jnp.where(live[:, None, None], window[:, 1:], tail)
    return _gated_out(c, z, lp), tail


@jax.named_scope("qkv")
def attention_project(x, lp, cfg: Lfm2MoeConfig, positions):
    """Queries, keys and values of ``x`` [N, D] at ``positions`` [N]: ``(q
    [N, H, d], k [N, Hkv, d], v [N, Hkv, d])``, q and k normed per head and
    then rotated."""
    n, hd = x.shape[0], cfg.head_dim
    inv = jnp.asarray(rope_frequencies(hd, cfg.rope_theta))
    h = rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(n, cfg.num_attention_heads, hd)
    k = (h @ lp["wk"]).reshape(n, cfg.num_key_value_heads, hd)
    v = (h @ lp["wv"]).reshape(n, cfg.num_key_value_heads, hd)
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    return apply_rope(q, positions, inv), apply_rope(k, positions, inv), v


# -- the stack ----------------------------------------------------------------

def output_logits(params: Params, cfg: Lfm2MoeConfig, x):
    """Float32 logits of the hidden rows ``x`` [..., D] through the final
    norm and the tied head."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["embedding_norm"], cfg.norm_eps)
        return jnp.matmul(x, params["embed"].astype(cfg.dtype).T,
                          preferred_element_type=jnp.float32)


def sequence_forward(params: Params, cfg: Lfm2MoeConfig, tokens, lengths,
                     starts, rec, attend: Callable):
    """A group of sequences (whole prompts, or chunks of prompts of
    different slots) through the stack in one pass.

    ``tokens`` [G, T], of which ``lengths`` [G] are real (0: an unused
    entry, which routes no pair to an expert), entry g's first at position
    ``starts[g]``; ``rec`` [conv layers, G, conv_L_cache - 1, D] is the
    convolutions' tails before each entry's first token (``starts`` and
    ``rec`` None: position 0 and zeros, a prompt's start).  The
    convolutions run per entry; everything a token does alone (norms,
    projections, the router, the experts' grouped product) runs on the
    G * T rows at once, so a layer streams each expert's matrices once for
    the group.  ``attend(m, q, k, v)`` stores the new ``k`` and ``v`` rows
    of attention layer ``m`` and returns the layer's output over whatever
    the caller keeps of the cache; it takes and returns the rows [G * T,
    ...], entry g's at [g * T, (g + 1) * T).  Returns ``(logits [G, V]
    float32 at each entry's last real token, rec)``."""
    g, t = tokens.shape
    positions = (jnp.tile(jnp.arange(t), g) if starts is None
                 else (starts[:, None] + jnp.arange(t)).reshape(-1))
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    x = params["embed"].astype(cfg.dtype)[tokens.reshape(-1)]
    zeros = jnp.zeros((cfg.conv_reach, cfg.hidden_size), cfg.dtype)
    new_tails = []
    for i, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        if kind == "conv":
            if rec is None:
                y, tail = jax.vmap(
                    lambda rows, n, lp=lp: conv_sequence(rows, lp, cfg, n,
                                                         zeros)
                )(x.reshape(g, t, -1), lengths)
            else:
                y, tail = jax.vmap(
                    lambda rows, n, before, lp=lp: conv_sequence(
                        rows, lp, cfg, n, before)
                )(x.reshape(g, t, -1), lengths, rec[len(new_tails)])
            y = y.reshape(g * t, -1)
            new_tails.append(tail)
        else:
            q, k, v = attention_project(x, lp, cfg, positions)
            y = attend(i - len(new_tails), q, k,
                       v).reshape(g * t, -1) @ lp["wo"]
        x, _ = _ffn(x + y, lp, cfg, live)
    last = x.reshape(g, t, -1)[jnp.arange(g), lengths - 1]
    return output_logits(params, cfg, last), (jnp.stack(new_tails)
                                              if new_tails else rec)


def prompts_forward(params: Params, cfg: Lfm2MoeConfig, tokens, lengths,
                    attend: Callable):
    """A group of whole prompts: :func:`sequence_forward` from position 0
    and zero tails."""
    return sequence_forward(params, cfg, tokens, lengths, None, None, attend)


def decode_step(params: Params, cfg: Lfm2MoeConfig, x, positions, live,
                rec, attend: Callable):
    """One token of every slot through the stack: ``x`` [B, D] at
    ``positions`` [B]; ``rec`` [conv layers, B, conv_L_cache - 1, D].
    ``attend(m, q, k, v)`` is attention layer ``m`` over whatever the caller
    keeps of the cache, after it has taken this step's ``k`` and ``v``
    [B, Hkv, d]; it returns [B, H, d].  Returns ``(hidden [B, D], rec,
    load)``; ``load`` sums :func:`moe_ffn`'s over the expert layers."""
    load = jnp.zeros((LOAD_FIELDS,), jnp.float32)
    j = 0
    for i, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        if kind == "conv":
            y, tail = conv_token(x, lp, cfg, live, rec[j])
            rec = rec.at[j].set(tail.astype(rec.dtype))
            j += 1
        else:
            q, k, v = attention_project(x, lp, cfg, positions)
            y = attend(i - j, q, k, v).reshape(x.shape[0], -1) @ lp["wo"]
        x, layer_load = _ffn(x + y, lp, cfg, live)
        if layer_load is not None:
            load = load + layer_load
    return x, rec, load


# -- a slot's state, and what a program counts (``serving/mixed.py``) --------

def init_rec(cfg: Lfm2MoeConfig, slots: int):
    """Zero tails of ``slots`` slots: [conv layers, slots, reach, D]."""
    return jnp.zeros((cfg.conv_layers, slots, cfg.conv_reach,
                      cfg.hidden_size), cfg.dtype)


def read_slots(rec, slots, fresh):
    """The tails of ``slots`` [G], zeros where ``fresh`` [G]."""
    return jnp.where(fresh[:, None, None], 0, rec[:, slots])


def write_slots(rec, slots, mine):
    """``rec`` with ``slots`` [G] holding ``mine``, a slot past the last
    dropped."""
    return rec.at[:, slots].set(mine.astype(rec.dtype), mode="drop")


def window_counts(cfg: Lfm2MoeConfig, load, live, steps: int):
    """A decode window's counts: its expert ``load`` alone."""
    return load


def scan_chunks(cfg: Lfm2MoeConfig, bucket: int) -> int:
    """A prompt program's blocks of chunked scan: none."""
    return 0
