"""A state-space / expert / attention decoder of the Nemotron-H kind
(``model_type: nemotron_h``): Mamba-2 mixers, routed relu-squared experts
with a shared one, and grouped-query attention without positions.

A block is ONE mixer behind one norm, ``x += mixer(norm(x))``, of the kind
``hybrid_override_pattern`` gives it:

* ``M``, Mamba-2: ``[z | xBC | dt] = W_in u``; a causal depthwise
  convolution of ``conv_kernel`` taps (with bias, then SiLU) over ``xBC``;
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the selective recurrence of ``ops/ssd.py`` over a state
  ``[heads, head_dim, state]`` in float32; ``y * silu(z)``, an RMSNorm over
  each of ``n_groups`` groups of channels, ``W_out``.  Its memory is the
  state and the last ``conv_kernel - 1`` rows of the pre-convolution
  ``xBC``, whatever the length;
* ``E``, experts: sigmoid scores over all ``n_routed_experts``, the top
  ``num_experts_per_tok`` chosen on ``score + bias``, the chosen scores
  normalised (``+ 1e-20`` in the sum) and scaled; an expert is two
  matrices, ``W_down relu(W_up u)^2`` (both stacks ``[experts, width,
  hidden]``: ``models/experts.py`` ``EXPERT_FORMS``); a shared expert of the
  same form is added unweighted.  This chip holds ``experts_held`` of the experts, from
  ``expert_offset`` (all, by default), through the sorted grouped product
  of ``models/experts.py``;
* ``*``, attention: GQA with NO rotary embedding and no other position
  signal (the state-space layers carry the order); its memory is a K and a
  V row a token.

Layers differ in kind, so the weights are a LIST of per-layer dicts and the
stack is a Python loop (as ``models/lfm2.py``).  The functions work on one
sequence [T, D] (prefill, a chunk of it) or on one token of every slot
[B, D] (a decode step); what the attention layers see of the cache is the
caller's (``attend``): the engine's programs (``serving/nemotron_h.py``)
hold the K/V pool, the states and the tails around them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dstack_tpu.models import experts
from dstack_tpu.models.experts import (  # noqa: F401  (LOAD_FIELDS: re-export)
    LOAD_FIELDS,
    expert_load,
    held_experts,
    relu2,
)
from dstack_tpu.ops import ssd
from dstack_tpu.ops.rmsnorm import rms_norm

Params = dict[str, Any]
#: a block's kind by its letter in ``hybrid_override_pattern``
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published ``config.json`` keys the forward reads
    (NVIDIA-Nemotron-3-Nano-30B-A3B's values), and what this chip holds of
    the experts."""
    vocab_size: int = 131_072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    n_routed_experts: int = 128
    experts_held: Optional[int] = None     # all
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    mlp_bias: bool = False
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262_144
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not name a mixer "
                f"(M | E | *) for each of {self.num_hidden_layers} blocks")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held",
                               self.n_routed_experts - self.expert_offset)
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("the held experts pass the router's last one")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must group evenly under kv heads")
        if (self.mamba_num_heads % self.n_groups
                or self.d_inner % self.n_groups):
            raise ValueError("the Mamba heads must group evenly")
        # published keys whose other value no code here computes
        for key, served in (("use_conv_bias", True),
                            ("mamba_proj_bias", False), ("mlp_bias", False),
                            ("attention_bias", False),
                            ("norm_topk_prob", True),
                            ("tie_word_embeddings", False),
                            ("n_shared_experts", 1)):
            if getattr(self, key) != served:
                raise ValueError(f"{key} is served as {served} only (the "
                                 "published value)")

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """Test size: every mechanism, no published width."""
        base = dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=6,
            hybrid_override_pattern="MEM*EM", mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
            n_routed_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=512, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)

    @classmethod
    def nemotron_3_nano_30b_a3b_9l_ep2(cls, **kw) -> "NemotronHConfig":
        """Published blocks 0-8 at every published width, experts 0-63 of
        128 and half the vocabulary: one chip of EP 2, one of six pipeline
        stages (``benchmarks/configs/nemotron-3-nano-30b-a3b-9l-ep2.json``)."""
        base = dict(num_hidden_layers=9,
                    hybrid_override_pattern=cls().hybrid_override_pattern[:9],
                    experts_held=64, vocab_size=65_536)
        base.update(kw)
        return cls(**base)

    # -- sizes ---------------------------------------------------------------
    @property
    def layer_kinds(self) -> tuple:
        return tuple(KINDS[c] for c in self.hybrid_override_pattern)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    @property
    def attention_layers(self) -> int:
        return self.layer_kinds.count("attention")

    @property
    def d_inner(self) -> int:
        """Channels of the recurrence: heads x head_dim (NOT ``expand x
        hidden_size``)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of ``xBC``, what the convolution runs over."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def conv_reach(self) -> int:
        """Rows of ``xBC`` a convolution keeps of the tokens before."""
        return self.conv_kernel - 1

    @property
    def kv_lanes(self) -> int:
        """A pool row's width: the kv heads folded into the lanes."""
        return self.num_key_value_heads * self.head_dim

    def block_params(self, kind: str) -> int:
        """Parameters of one block held HERE, its norm with it."""
        d = self.hidden_size
        if kind == "mamba":
            return (d * (2 * self.d_inner + 2 * self.n_groups
                         * self.ssm_state_size + self.mamba_num_heads)
                    + (self.conv_kernel + 1) * self.conv_dim
                    + 3 * self.mamba_num_heads + self.d_inner
                    + self.d_inner * d + d)
        if kind == "experts":
            return (d * self.n_routed_experts + self.n_routed_experts
                    + self.experts_held * 2 * d * self.moe_intermediate_size
                    + 2 * d * self.moe_shared_expert_intermediate_size + d)
        q = self.num_attention_heads * self.head_dim
        return 2 * d * q + 2 * d * self.kv_lanes + d

    def num_params(self) -> int:
        """Parameters held HERE (``experts_held`` of the experts, the
        embedding and the untied head at ``vocab_size`` rows each)."""
        d = self.hidden_size
        return (2 * self.vocab_size * d + d
                + sum(self.block_params(kind) for kind in self.layer_kinds))

    def recurrent_state_bytes(self, slots: int) -> int:
        """The state-space state (float32) and the convolution tails (the
        served type) of ``slots`` slots over the Mamba layers."""
        state = (self.mamba_num_heads * self.mamba_head_dim
                 * self.ssm_state_size * 4)
        tail = (self.conv_reach * self.conv_dim
                * jnp.dtype(self.dtype).itemsize)
        return self.mamba_layers * slots * (state + tail)


def init_params(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """Random weights in the tree layout the engine's ``params=`` takes.
    ``A_log``, ``dt_bias`` and ``D`` as the published initialisation draws
    them: ``A`` uniform in 1..heads, ``dt`` log-uniform in ``time_step_min
    .. time_step_max`` (floored) through the inverse softplus, ``D`` ones."""
    d, dt = cfg.hidden_size, cfg.dtype
    q = cfg.num_attention_heads * cfg.head_dim
    h = cfg.mamba_num_heads
    f32 = jnp.float32

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, f32) * fan_in ** -0.5).astype(dt)

    def layer(key, kind):
        k = jax.random.split(key, 8)
        lp = {"norm": jnp.ones((d,), dt)}
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(
                k[2], (h,), f32, jnp.log(cfg.time_step_min),
                jnp.log(cfg.time_step_max)))
            step = jnp.maximum(step, cfg.time_step_floor)
            lp.update(
                w_in=dense(k[0], (d, 2 * cfg.d_inner + 2 * cfg.n_groups
                                  * cfg.ssm_state_size + h), d),
                conv_w=dense(k[1], (cfg.conv_kernel, cfg.conv_dim),
                             cfg.conv_kernel),
                conv_b=jnp.zeros((cfg.conv_dim,), dt),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.log(jax.random.uniform(k[3], (h,), f32, 1.0,
                                                 float(h))),
                D=jnp.ones((h,), f32),
                gate_norm=jnp.ones((cfg.d_inner,), dt),
                w_out=dense(k[4], (cfg.d_inner, d), cfg.d_inner))
        elif kind == "experts":
            e, f = cfg.experts_held, cfg.moe_intermediate_size
            fs = cfg.moe_shared_expert_intermediate_size
            lp.update(
                router=dense(k[0], (d, cfg.n_routed_experts), d),
                router_bias=jnp.zeros((cfg.n_routed_experts,), f32),
                we_up=dense(k[1], (e, f, d), d),
                we_down=dense(k[2], (e, f, d), f),
                ws_up=dense(k[3], (d, fs), d),
                ws_down=dense(k[4], (fs, d), fs))
        else:
            lp.update(
                wq=dense(k[0], (d, q), d),
                wk=dense(k[1], (d, cfg.kv_lanes), d),
                wv=dense(k[2], (d, cfg.kv_lanes), d),
                wo=dense(k[3], (q, d), q))
        return lp

    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    keys = jax.random.split(k_layers, cfg.num_hidden_layers)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "head": dense(k_head, (d, cfg.vocab_size), d),
        "final_norm": jnp.ones((d,), dt),
        "layers": [layer(keys[i], kind)
                   for i, kind in enumerate(cfg.layer_kinds)],
    }


# -- experts ------------------------------------------------------------------

def route(h, lp, cfg: NemotronHConfig):
    """Experts and weights of every token of ``h`` [T, D] over all
    ``n_routed_experts`` (``experts.route``; ``1e-20`` in the weights'
    sum)."""
    return experts.route(
        h, lp["router"], lp["router_bias"], top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, eps=1e-20, n_group=cfg.n_group,
        topk_group=cfg.topk_group)


def moe_block(h, lp, cfg: NemotronHConfig, token_mask=None):
    """The routed experts held here and the shared expert, for ``h``
    [T, D]: ``(y, load)``; ``load`` is :func:`experts.expert_load`'s vector
    over the unmasked tokens."""
    ids, weights = route(h, lp, cfg)
    y, counts = held_experts(h, ids, weights, lp, cfg, token_mask,
                             form="relu2")
    with jax.named_scope("shared_expert"):
        y = y + relu2(h, lp["ws_up"], lp["ws_down"])
    tokens = (h.shape[0] if token_mask is None
              else token_mask.sum().astype(jnp.float32))
    return y, expert_load(counts, tokens, cfg)


# -- Mamba-2 ------------------------------------------------------------------

def _mamba_in(h, lp, cfg: NemotronHConfig):
    """``(z, xBC, dt)`` of the normed input ``h`` [..., D]: the gate, the
    convolution's input (in the served type: the form its tail is kept in)
    and the raw step of every head."""
    z, xbc, dt = jnp.split(
        h @ lp["w_in"], [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    return z, xbc, dt


def _mamba_split(conv, dt, lp, cfg: NemotronHConfig):
    """The recurrence's operands from the convolution's output ``conv``
    [..., conv_dim] and the raw steps ``dt`` [..., H]: ``(x [..., H, P], dt
    float32 [..., H], A [H], B, C [..., G, N])``."""
    lead = conv.shape[:-1]
    g, n = cfg.n_groups, cfg.ssm_state_size
    x, b, c = jnp.split(conv, [cfg.d_inner, cfg.d_inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    return (x.reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim)), dt,
            -jnp.exp(lp["A_log"]), b.reshape(lead + (g, n)),
            c.reshape(lead + (g, n)))


def _mamba_out(y, z, lp, cfg: NemotronHConfig):
    """Gate FIRST, then an RMSNorm over each of ``n_groups`` groups of
    channels, the weight, ``W_out``."""
    lead = y.shape[:-2]
    y = y.reshape(lead + (cfg.d_inner,)).astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(lead + (cfg.n_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), -1, keepdims=True)
        + cfg.layer_norm_epsilon)
    y = grouped.reshape(y.shape) * lp["gate_norm"].astype(jnp.float32)
    return y.astype(z.dtype) @ lp["w_out"]


def mamba_sequence(h, lp, cfg: NemotronHConfig, length, state, tail):
    """The Mamba-2 mixer over one sequence ``h`` [T, D] (normed) of which
    ``length`` tokens are real, behind ``state`` [H, P, N] float32 and
    ``tail`` [conv_kernel - 1, conv_dim] (the pre-convolution ``xBC`` rows
    of the tokens just before; zeros at a prompt's start).  Returns ``(y,
    state, tail)`` after the last real token: the padded positions of a
    bucket do not advance the state (``ops/ssd.py``: their ``dt`` is 0)."""
    t = h.shape[0]
    z, xbc, dt = _mamba_in(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        padded = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=0)
        taps = lp["conv_w"].astype(jnp.float32)
        # token i sits at row i + reach and sees itself under the LAST tap
        conv = sum(taps[j] * padded[j:j + t] for j in range(cfg.conv_kernel))
        conv = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
        tail = jax.lax.dynamic_slice_in_dim(padded, length, cfg.conv_reach,
                                            axis=0)
    with jax.named_scope("ssm_scan"):
        x, dt, a, b, c = _mamba_split(conv.astype(h.dtype), dt, lp, cfg)
        y, state = ssd.ssd_chunked(x, dt, a, b, c, lp["D"], state, length,
                                   chunk=cfg.chunk_size)
    return _mamba_out(y, z, lp, cfg), state, tail


def mamba_token(h, lp, cfg: NemotronHConfig, live, state, tail):
    """The Mamba-2 mixer for one token of every slot: ``h`` [B, D]
    (normed), ``state`` [B, H, P, N] float32, ``tail`` [B, conv_kernel - 1,
    conv_dim].  Slots that are not ``live`` keep their state (their ``dt``
    is 0: decay 1, no input) and their tail."""
    z, xbc, dt = _mamba_in(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([tail.astype(xbc.dtype), xbc[:, None]],
                                 axis=1)
        conv = jnp.einsum("bjc,jc->bc", window.astype(jnp.float32),
                          lp["conv_w"].astype(jnp.float32))
        conv = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
        tail = jnp.where(live[:, None, None], window[:, 1:], tail)
    with jax.named_scope("ssm_step"):
        x, dt, a, b, c = _mamba_split(conv.astype(h.dtype), dt, lp, cfg)
        dt = jnp.where(live[:, None], dt, 0.0)
        y, state = ssd.ssd_step(x, dt, a, b, c, lp["D"], state)
    return _mamba_out(y, z, lp, cfg), state, tail


# -- attention ----------------------------------------------------------------

@jax.named_scope("qkv")
def attention_project(h, lp, cfg: NemotronHConfig):
    """Queries, keys and values of the normed ``h`` [N, D]: ``(q [N, H, d],
    k [N, Hkv, d], v [N, Hkv, d])``.  No rotary embedding: the model gives
    its attention no position signal."""
    n, hd = h.shape[0], cfg.head_dim
    return ((h @ lp["wq"]).reshape(n, cfg.num_attention_heads, hd),
            (h @ lp["wk"]).reshape(n, cfg.num_key_value_heads, hd),
            (h @ lp["wv"]).reshape(n, cfg.num_key_value_heads, hd))


# -- the stack ----------------------------------------------------------------

def output_logits(params: Params, cfg: NemotronHConfig, x):
    """Float32 logits of the hidden rows ``x`` [..., D] through the final
    norm and the untied head."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.layer_norm_epsilon)
        return jnp.matmul(x, params["head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _stack(params: Params, cfg: NemotronHConfig, x, live, rec, attend,
           mamba):
    """The blocks over ``x``: ``mamba(h, lp, state, tail)`` is the Mamba-2
    mixer of the caller's form.  ``rec`` = {"ssm", "tail"}: a list each,
    one entry a Mamba layer.  Returns ``(x, rec, load)``."""
    load = jnp.zeros((LOAD_FIELDS,), jnp.float32)
    states, tails = [], []
    attention = 0
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        h = rms_norm(x, lp["norm"], cfg.layer_norm_epsilon)
        if kind == "mamba":
            j = len(states)
            y, state, tail = mamba(h, lp, rec["ssm"][j], rec["tail"][j])
            states.append(state)
            tails.append(tail.astype(rec["tail"][j].dtype))
        elif kind == "experts":
            y, layer_load = moe_block(h, lp, cfg, live)
            load = load + layer_load
        else:
            q, k, v = attention_project(h, lp, cfg)
            y = attend(attention, q, k, v).reshape(x.shape[0], -1) @ lp["wo"]
            attention += 1
        x = x + y
    return x, {"ssm": states, "tail": tails}, load


def sequence_forward(params: Params, cfg: NemotronHConfig, tokens, length,
                     rec, attend: Callable):
    """One sequence (a whole prompt, or one chunk of it) through the stack.

    ``tokens`` [T] of which ``length`` are real; ``rec`` is the Mamba
    layers' memory before the first token: {"ssm": [[H, P, N] float32, ...],
    "tail": [[conv_kernel - 1, conv_dim], ...]}, one entry a Mamba layer.
    ``attend(m, q, k, v)`` stores the new ``k`` and ``v`` rows of attention
    layer ``m`` and returns the layer's output [T, H, d] over whatever the
    caller keeps of the cache.  Returns ``(logits [V] float32 at the last
    real token, rec)``."""
    live = jnp.arange(tokens.shape[0]) < length
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, rec, _ = _stack(
        params, cfg, x, live, rec, attend,
        lambda h, lp, state, tail: mamba_sequence(h, lp, cfg, length, state,
                                                  tail))
    return output_logits(params, cfg, x[length - 1]), rec


def decode_step(params: Params, cfg: NemotronHConfig, x, live, rec,
                attend: Callable):
    """One token of every slot through the stack: ``x`` [B, D]; ``rec`` as
    :func:`sequence_forward`'s with a slot axis in front of every entry.
    ``attend(m, q, k, v)`` is attention layer ``m`` over whatever the caller
    keeps of the cache, after it has taken this step's ``k`` and ``v``
    [B, Hkv, d]; it returns [B, H, d].  Returns ``(hidden [B, D], rec,
    load)``; ``load`` sums :func:`moe_block`'s over the expert layers."""
    return _stack(
        params, cfg, x, live, rec, attend,
        lambda h, lp, state, tail: mamba_token(h, lp, cfg, live, state, tail))
