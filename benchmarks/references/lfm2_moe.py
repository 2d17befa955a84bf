"""Plain reference of the LFM2-MoE language model's block (HF
``modeling_lfm2_moe``), as the configuration ``lfm2-24b-a2b-9l`` cuts it:
pre-norm residual layers whose mixer is a gated short convolution or, every
fourth, grouped-query attention, and whose feed-forward is a dense SwiGLU
first and then 64 sigmoid-routed experts, 4 a token, no shared one.

Straightforward ``jax.numpy``: float32, matrix products at ``highest``, no
cache, no kernel, one sequence at a time, every held expert computed for
every token and masked.  It imports nothing of the program.  ``Sizes``
carries a dense decoder's numbers only, so the rest is read from the
configuration's own file (``config=`` hands another one in: the CPU tests
run this file at a toy size).  The weights are the benchmark's own, made
here from the seed in the tree layout the program's ``params=`` takes; a
layer is upcast when it is used.  The forward runs a layer at a time over
the whole sequence (the attention a head at a time, the experts an expert
at a time), so the longest sampled request, 3,072 tokens, fits beside the
weights.

The layer equations (each reading the catalog's keys leave open is listed
under ``assumed`` in the configuration file).  RMSNorm is ``x *
rsqrt(mean(x^2) + norm_eps) * w``::

    h  = x + mixer(rms(x, operator_norm))
    x' = h + ffn(rms(h, ffn_norm))
    conv:       [B, C, X] = split3(W_in u)
                z_t = sum_{j=0..2} w[j] * (B * X)_{t-2+j}    zeros before t = 0
                y = W_out (C * z)
    attention:  q, k, v = W_q u, W_k u, W_v u     32 / 8 / 8 heads of 64
                q, k = rms per head (q_norm, k_norm), then rotate-half RoPE
                causal softmax(q k^T / sqrt(64)) v, grouped 4 : 1;  W_o
    dense ffn:  W_down(silu(W_gate h) * W_up h)
    experts:    s = sigmoid(W_r h);  ids = top4(s + expert_bias)
                w = s[ids] / (sum s[ids] + 1e-6) * routed_scaling_factor
                y = sum_k w_k E_{ids_k}(h)         each a SwiGLU of width 1536
    output:     logits = rms(x_L, embedding_norm) E^T       (tied)

The chip holds ``num_experts_held`` of ``num_experts`` experts from
``expert_offset`` (all 64 in the benchmark's configuration; the CPU tests
cut shares); what absent ones would add is left out, here as in the program.

The selection bias is what training leaves in the published model
(``use_expert_bias``): the values under which every expert gets the same
share of the tokens.  ``init_weights`` has no training run, so it fits them
as ``ling_hybrid.py`` does: ``CALIBRATION`` sequences of uniform token ids
from the seed go through the layers made so far, and each router's bias is
moved against its experts' loads until they are even.

``lower`` computes the same forward in the nearest precision below the
stated one: both operands of every matrix product rounded to the int8 grid
for a bfloat16 model, to bfloat16 for a float32 one.  It is the control the
comparison has to fail; the benchmark's own runs never call it.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# the arithmetic the plain references of the sparse decoders share: products
# in float32 at ``highest`` (or rounded to the next lower grid first), RMSNorm,
# rotate-half RoPE over the whole last dim, SwiGLU, a router's sigmoid scores,
# the padding quantum, and how the selection bias is fitted: CALIBRATION
# sequences x tokens (here 256 pairs an expert at 64 experts and 4 a token),
# BALANCE_PASSES passes each moving the bias by RATE * DECAY ** pass *
# log(load / even load)
from benchmarks.references.ling_hybrid import (
    HIGHEST,
    BALANCE_DECAY,
    BALANCE_PASSES,
    BALANCE_RATE,
    CALIBRATION,
    LENGTH_QUANTUM,
    _mm,
    _rms_norm,
    _rope,
    _scores,
    _swiglu,
)

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "lfm2-24b-a2b-9l.json")


def _shape(sizes, config=None) -> dict:
    """What ``Sizes`` lacks, by the configuration file's published keys."""
    c = config or json.loads(CONFIG_FILE.read_text())
    return {
        "d": sizes.hidden, "ffn": sizes.ffn, "heads": sizes.heads,
        "kv_heads": sizes.kv_heads, "hd": sizes.head_dim,
        "vocab": sizes.vocab, "eps": sizes.rms_eps, "theta": sizes.rope_theta,
        "dtype": sizes.dtype, "types": tuple(c["layer_types"]),
        "dense": c["num_dense_layers"], "conv": c["conv_L_cache"],
        "routed": c["num_experts"],
        "held": c.get("num_experts_held", c["num_experts"]),
        "offset": c.get("expert_offset", 0),
        "topk": c["num_experts_per_tok"],
        "scale": c["routed_scaling_factor"],
        "f_expert": c["moe_intermediate_size"],
    }


def init_weights(sizes, seed: int, config=None) -> dict:
    """All weights from the seed, on the device, in the served type: one
    jitted call a layer, so that the float32 temporaries are one layer's.
    The routers' selection bias is fitted last (``_fit_selection_bias``)."""
    s = _shape(sizes, config)
    dtype = jnp.dtype(s["dtype"])
    d, hd = s["d"], s["hd"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd

    def dense(key, shape, fan_in):
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return x.astype(dtype)

    def layer(key, kind, dense_ffn):
        k = jax.random.split(key, 12)
        lw = {"operator_norm": jnp.ones((d,), dtype),
              "ffn_norm": jnp.ones((d,), dtype)}
        if kind == "conv":
            lw.update(w_in=dense(k[0], (d, 3 * d), d),
                      conv_w=dense(k[1], (s["conv"], d), s["conv"]),
                      w_out=dense(k[2], (d, d), d))
        else:
            lw.update(wq=dense(k[0], (d, q), d), wk=dense(k[1], (d, kv), d),
                      wv=dense(k[2], (d, kv), d), wo=dense(k[3], (q, d), q),
                      q_norm=jnp.ones((hd,), dtype),
                      k_norm=jnp.ones((hd,), dtype))
        if dense_ffn:
            f = s["ffn"]
            lw.update(w_gate=dense(k[6], (d, f), d),
                      w_up=dense(k[7], (d, f), d),
                      w_down=dense(k[8], (f, d), f))
        else:
            e, f = s["held"], s["f_expert"]
            lw.update(
                router=dense(k[6], (d, s["routed"]), d),
                router_bias=jnp.zeros((s["routed"],), jnp.float32),
                we_gate=dense(k[7], (e, d, f), d),
                we_up=dense(k[8], (e, d, f), d),
                we_down=dense(k[9], (e, f, d), f))
        return lw

    def ends(key):
        return {"embed": dense(key, (s["vocab"], d), d),
                "embedding_norm": jnp.ones((d,), dtype)}

    # the counter-based generator of XLA: several times faster on the chip
    # than the default threefry for billions of values
    keys = jax.random.split(jax.random.key(int(seed), impl="rbg"),
                            len(s["types"]) + 2)
    make = jax.jit(layer, static_argnums=(1, 2))
    tree = jax.jit(ends)(keys[0])
    tree["layers"] = [make(keys[i + 1], kind, i < s["dense"])
                      for i, kind in enumerate(s["types"])]
    _fit_selection_bias(tree, s, keys[-1])
    return tree


def _fit_selection_bias(tree, s, key) -> None:
    """Set every router's ``router_bias`` so that its experts' loads are even
    over the calibration tokens, layer by layer: a router is fitted on the
    hidden states that the layers before it, fitted already, give."""
    count, length = CALIBRATION
    ids = jax.random.randint(key, (count, length), 0, s["vocab"])
    xs = [tree["embed"][row].astype(jnp.float32) for row in ids]
    shape = tuple(sorted((k, v) for k, v in s.items()))
    for kind, lw in zip(s["types"], tree["layers"]):
        if "router" in lw:
            score = jnp.concatenate(
                [_layer(x, lw, kind=kind, shape=shape, lower=None,
                        scores=True) for x in xs])
            lw["router_bias"] = _even_bias(score, shape=shape)
        xs = [_layer(x, lw, kind=kind, shape=shape, lower=None) for x in xs]


def _conv(h, lw, s, lower):
    t = h.shape[0]
    b, c, x = jnp.split(_mm(h, lw["w_in"], lower), 3, axis=-1)
    taps = lw["conv_w"].astype(jnp.float32)
    # token t sees itself under the LAST tap and t - j under the j-th before
    past = jnp.concatenate(
        [jnp.zeros((s["conv"] - 1, b.shape[1]), jnp.float32), b * x])
    z = sum(taps[j] * past[j:j + t] for j in range(s["conv"]))
    return _mm(c * z, lw["w_out"], lower)


def _attention(h, lw, s, lower):
    t, n, nkv, hd = h.shape[0], s["heads"], s["kv_heads"], s["hd"]
    positions = jnp.arange(t)
    q = _mm(h, lw["wq"], lower).reshape(t, n, hd)
    k = _mm(h, lw["wk"], lower).reshape(t, nkv, hd)
    v = _mm(h, lw["wv"], lower).reshape(t, nkv, hd)
    q = _rope(_rms_norm(q, lw["q_norm"], s["eps"]), positions, s["theta"])
    k = _rope(_rms_norm(k, lw["k_norm"], s["eps"]), positions, s["theta"])
    causal = positions[None, :] <= positions[:, None]
    # query head i reads kv head i // (n / nkv)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))

    def head(args):
        qh, kh, vh = args                       # [T, d] of one head
        score = jnp.matmul(qh, kh.T, precision=HIGHEST) * hd ** -0.5
        score = jnp.where(causal, score, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(score, -1), vh, precision=HIGHEST)

    per_head = lambda a: jnp.moveaxis(a, 1, 0)
    o = jax.lax.map(head, (per_head(q), per_head(k), per_head(v)))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(t, n * hd), lw["wo"], lower)


def _chosen(choose, s):
    """The experts [T, k] each token picks by its selection scores ``choose``
    [T, E]: the top ``topk``, no groups."""
    return jax.lax.top_k(choose, s["topk"])[1]


@functools.partial(jax.jit, static_argnames=("shape",))
def _even_bias(score, *, shape):
    """The selection bias [E] under which the tokens of ``score`` [T, E] load
    every expert alike: from zeros, each pass counts the pairs an expert gets
    and moves its bias against the logarithm of its share of the even load
    (the sign rule of auxiliary-loss-free balancing, scaled by the miss)."""
    s = dict(shape)
    t, e = score.shape
    even = t * s["topk"] / e

    def one_pass(i, bias):
        load = jnp.zeros((e,), jnp.float32).at[
            _chosen(score + bias, s)].add(1.0)
        return bias - (BALANCE_RATE * BALANCE_DECAY ** i
                       * jnp.log((load + 1.0) / (even + 1.0)))

    return jax.lax.fori_loop(0, BALANCE_PASSES, one_pass,
                             jnp.zeros((e,), jnp.float32))


def _experts(h, lw, s, lower):
    t = h.shape[0]
    score = _scores(h, lw, lower)
    chosen = _chosen(score + lw["router_bias"], s)               # [T, k]
    picked = jnp.take_along_axis(score, chosen, 1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-6) * s["scale"]
    # weight of every routed expert for every token, 0 where not chosen
    full = jnp.zeros((t, s["routed"]), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(weight)
    mine = full[:, s["offset"]:s["offset"] + s["held"]]

    def add(total, expert):
        gate, up, down, w = expert
        return total + w[:, None] * _swiglu(h, gate, up, down, lower), None

    routed, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (lw["we_gate"], lw["we_up"], lw["we_down"], mine.T))
    return routed


@functools.partial(jax.jit,
                   static_argnames=("kind", "shape", "lower", "scores"))
def _layer(x, lw, *, kind, shape, lower, scores=False):
    """One block over a whole sequence x [T, hidden], causal; with ``scores``
    the router's scores [T, E] of the block's tokens instead."""
    s = dict(shape)
    h = _rms_norm(x, lw["operator_norm"], s["eps"])
    x = x + (_conv if kind == "conv" else _attention)(h, lw, s, lower)
    h = _rms_norm(x, lw["ffn_norm"], s["eps"])
    if scores:
        return _scores(h, lw, lower)
    if "router" in lw:
        return x + _experts(h, lw, s, lower)
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, embed, *, eps, lower):
    return _mm(_rms_norm(x, final_norm, eps), embed.T, lower)


def logits(weights: dict, sizes, tokens, first: int, count: int,
           lower: bool = False, config=None) -> np.ndarray:
    """Float32 logits [count, vocab] at positions ``first .. first+count-1``
    of the sequence ``tokens``: the scores of the token that FOLLOWS each of
    those positions.  One full causal forward, layer by layer."""
    s = _shape(sizes, config)
    tokens = np.asarray(tokens, np.int32)
    lower = s["dtype"] if lower else None
    t = len(tokens)
    padded = -(-t // LENGTH_QUANTUM) * LENGTH_QUANTUM
    # trailing padding cannot reach an earlier position: the convolution
    # and the attention mask are causal, experts per token
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    shape = tuple(sorted((k, v) for k, v in s.items()))
    for kind, lw in zip(s["types"], weights["layers"]):
        x = _layer(x, lw, kind=kind, shape=shape, lower=lower)
    out_pad = -(-count // 64) * 64
    rows = np.minimum(np.arange(first, first + out_pad), padded - 1)
    out = _head(x[jnp.asarray(rows)], weights["embedding_norm"],
                weights["embed"], eps=s["eps"], lower=lower)
    return np.asarray(out[:count])
