"""Plain reference of the Nemotron-H language model's block (HF
``modeling_nemotron_h``), as the configuration
``nemotron-3-nano-30b-a3b-9l-ep2`` cuts it: residual blocks of ONE mixer
behind one norm, the mixer a Mamba-2 state-space layer, or routed
relu-squared experts with a shared one, or grouped-query attention without
positions, as ``hybrid_override_pattern`` says.

Straightforward ``jax.numpy``: float32, matrix products at ``highest``, no
cache, no kernel, one sequence at a time, the state-space layer as its
TOKEN-BY-TOKEN recurrence (a ``lax.scan`` over the tokens; never the chunked
form, which is what this file checks), every held expert computed for every
token and masked.  It imports nothing of the program.  ``Sizes`` carries a
dense decoder's numbers only, so the rest is read from the configuration's
own file (``config=`` hands another one in: the CPU tests run this file at
a toy size).  The weights are the benchmark's own, made here from the seed
in the tree layout the program's ``params=`` takes; a layer is upcast when
it is used.  The forward runs a layer at a time over the whole sequence (the
attention a head at a time, the experts an expert at a time), so the longest
sampled request, 3,072 tokens, fits beside the weights.

The layer equations (each reading the catalog's keys leave open is listed
under ``assumed`` in the configuration file).  RMSNorm is ``x *
rsqrt(mean(x^2) + layer_norm_epsilon) * w``; no bias but the convolution's::

    x' = x + mixer(rms(x, norm))
    M:  [z | xBC | dt] = W_in u          widths d_inner | d_inner + 2 G N | H
        xBC_t = silu(b + sum_{j=0..3} w[j] * xBC_{t-3+j})   zeros before t = 0
        [x | B | C] = xBC                x [H, P];  B, C [G, N];  head h reads
                                         group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)        one a head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         S [H, P, N]
        y_t = S_t C_t + D x_t
        y = rms_groups(y * silu(z)) * w_gate_norm            G groups of
                                                             d_inner / G
        out = W_out y
    E:  s = sigmoid(W_r u);  ids = top6(s + e_score_correction_bias)
        w = s[ids] / (sum s[ids] + 1e-20) * routed_scaling_factor
        out = sum_k w_k E_{ids_k}(u) + E_shared(u),  E(u) = W_down relu(W_up u)^2
        (the routed stacks are kept [experts, width, hidden], up and down alike)
    *:  q, k, v = W_q u, W_k u, W_v u    32 / 2 / 2 heads of 128, NO rotary
        causal softmax(q k^T / sqrt(128)) v, 16 queries a kv head;  W_o
    output:  logits = rms(x_L, final_norm) W_head            (untied)

The chip holds ``n_routed_experts`` of the router's ``router_experts``
experts from ``expert_offset`` (64 of 128 in the benchmark's configuration,
whose file gives the held count under the published key; the CPU tests cut
other shares); what absent ones would add is left out, here as in the
program.

``A_log``, ``dt_bias`` and ``D`` are drawn as the published initialisation
draws them (``A`` uniform in 1..H, ``dt`` log-uniform in ``time_step_min ..
time_step_max`` floored at ``time_step_floor`` and put through the inverse
softplus, ``D`` ones): a head's state then remembers tens to hundreds of
tokens, and a random model's states neither vanish nor blow up.

The selection bias is what training leaves in the published model
(``e_score_correction_bias``): the values under which every expert gets the
same share of the tokens.  ``init_weights`` has no training run, so it fits
them as ``ling_hybrid.py`` does: ``CALIBRATION`` sequences of uniform token
ids from the seed go through the layers made so far, and each router's bias
is moved against its experts' loads until they are even.

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
# a router's sigmoid scores, the padding quantum, and how the selection bias
# is fitted (``lfm2_moe._even_bias``: no groups, as here): CALIBRATION
# sequences x tokens (here 192 pairs an expert at 128 experts and 6 a token),
# 40 passes each moving the bias against log(load / even load)
from benchmarks.references.lfm2_moe import _even_bias
from benchmarks.references.ling_hybrid import (
    HIGHEST,
    CALIBRATION,
    LENGTH_QUANTUM,
    _mm,
    _rms_norm,
    _scores,
)

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "nemotron-3-nano-30b-a3b-9l-ep2.json")
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def _shape(sizes, config=None) -> dict:
    """What ``Sizes`` lacks, by the configuration file's published keys."""
    c = config or json.loads(CONFIG_FILE.read_text())
    if c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("grouped selection is not written here: the "
                         "published model has one group")
    return {
        "d": sizes.hidden, "heads": sizes.heads, "kv_heads": sizes.kv_heads,
        "hd": sizes.head_dim, "vocab": sizes.vocab, "eps": sizes.rms_eps,
        "dtype": sizes.dtype,
        "types": tuple(KINDS[k] for k in c["hybrid_override_pattern"]),
        "m_heads": c["mamba_num_heads"], "m_hd": c["mamba_head_dim"],
        "groups": c["n_groups"], "state": c["ssm_state_size"],
        "conv": c["conv_kernel"],
        "dt_min": c["time_step_min"], "dt_max": c["time_step_max"],
        "dt_floor": c["time_step_floor"],
        "routed": c["router_experts"], "held": c["n_routed_experts"],
        "offset": c["expert_offset"],
        "topk": c["num_experts_per_tok"],
        "scale": c["routed_scaling_factor"],
        "f_expert": c["moe_intermediate_size"],
        "f_shared": c["moe_shared_expert_intermediate_size"],
    }


def _mamba_widths(s: dict) -> tuple:
    """``(d_inner, conv channels)``: heads x head_dim, and ``xBC``'s."""
    inner = s["m_heads"] * s["m_hd"]
    return inner, inner + 2 * s["groups"] * s["state"]


def init_weights(sizes, seed: int, config=None) -> dict:
    """All weights from the seed, on the device, in the served type: one
    jitted call a layer, so that the float32 temporaries are one layer's.
    The routers' selection bias is fitted last (``_fit_selection_bias``)."""
    s = _shape(sizes, config)
    dtype = jnp.dtype(s["dtype"])
    d, hd, h = s["d"], s["hd"], s["m_heads"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    inner, channels = _mamba_widths(s)
    f32 = jnp.float32

    def dense(key, shape, fan_in):
        x = jax.random.normal(key, shape, f32) * fan_in ** -0.5
        return x.astype(dtype)

    def layer(key, kind):
        k = jax.random.split(key, 8)
        lw = {"norm": jnp.ones((d,), dtype)}
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(
                k[2], (h,), f32, np.log(s["dt_min"]), np.log(s["dt_max"])))
            step = jnp.maximum(step, s["dt_floor"])
            lw.update(
                w_in=dense(k[0], (d, inner + channels + h), d),
                conv_w=dense(k[1], (s["conv"], channels), s["conv"]),
                conv_b=(jax.random.normal(k[5], (channels,), f32)
                        * 0.1).astype(dtype),
                # softplus(dt_bias) = step
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.log(jax.random.uniform(k[3], (h,), f32, 1.0,
                                                 float(h))),
                D=jnp.ones((h,), f32),
                gate_norm=jnp.ones((inner,), dtype),
                w_out=dense(k[4], (inner, d), inner))
        elif kind == "experts":
            e, f, fs = s["held"], s["f_expert"], s["f_shared"]
            lw.update(
                router=dense(k[0], (d, s["routed"]), d),
                router_bias=jnp.zeros((s["routed"],), f32),
                we_up=dense(k[1], (e, f, d), d),
                we_down=dense(k[2], (e, f, d), f),
                ws_up=dense(k[3], (d, fs), d),
                ws_down=dense(k[4], (fs, d), fs))
        else:
            lw.update(wq=dense(k[0], (d, q), d), wk=dense(k[1], (d, kv), d),
                      wv=dense(k[2], (d, kv), d), wo=dense(k[3], (q, d), q))
        return lw

    def ends(key):
        k = jax.random.split(key)
        return {"embed": dense(k[0], (s["vocab"], d), d),
                "head": dense(k[1], (d, s["vocab"]), d),
                "final_norm": jnp.ones((d,), dtype)}

    # the counter-based generator of XLA: several times faster on the chip
    # than the default threefry for billions of values
    keys = jax.random.split(jax.random.key(int(seed), impl="rbg"),
                            len(s["types"]) + 2)
    make = jax.jit(layer, static_argnums=(1,))
    tree = jax.jit(ends)(keys[0])
    tree["layers"] = [make(keys[i + 1], kind)
                      for i, kind in enumerate(s["types"])]
    _fit_selection_bias(tree, s, keys[-1])
    return tree


def _fit_selection_bias(tree, s, key) -> None:
    """Set every router's ``router_bias`` so that its experts' loads are even
    over the calibration tokens, block by block: a router is fitted on the
    hidden states that the blocks before it, fitted already, give."""
    count, length = CALIBRATION
    ids = jax.random.randint(key, (count, length), 0, s["vocab"])
    xs = [tree["embed"][row].astype(jnp.float32) for row in ids]
    shape = tuple(sorted((k, v) for k, v in s.items()))
    for kind, lw in zip(s["types"], tree["layers"]):
        if kind == "experts":
            score = jnp.concatenate(
                [_layer(x, lw, kind=kind, shape=shape, lower=None,
                        scores=True) for x in xs])
            lw["router_bias"] = _even_bias(score, shape=shape)
        xs = [_layer(x, lw, kind=kind, shape=shape, lower=None) for x in xs]


def _mamba(h, lw, s, lower):
    t = h.shape[0]
    heads, p, g, n = s["m_heads"], s["m_hd"], s["groups"], s["state"]
    inner, channels = _mamba_widths(s)
    z, xbc, dt = jnp.split(_mm(h, lw["w_in"], lower),
                           [inner, inner + channels], axis=-1)
    taps = lw["conv_w"].astype(jnp.float32)
    # token t sees itself under the LAST tap and t - j under the j-th before
    past = jnp.concatenate(
        [jnp.zeros((s["conv"] - 1, channels), jnp.float32), xbc])
    xbc = jax.nn.silu(lw["conv_b"].astype(jnp.float32) + sum(
        taps[j] * past[j:j + t] for j in range(s["conv"])))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(t, heads, p)
    # head h reads group h // (heads / g)
    b, c = (jnp.repeat(a.reshape(t, g, n), heads // g, axis=1)
            for a in (b, c))
    dt = jax.nn.softplus(dt + lw["dt_bias"])                     # [T, H]
    a = -jnp.exp(lw["A_log"])                                    # [H]

    def token(state, now):
        x_t, b_t, c_t, dt_t = now
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, (state * c_t[:, None, :]).sum(-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                        (x, b, c, dt))
    y = (y + lw["D"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)
    # gate first, then an RMSNorm over each group of channels
    y = y.reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + s["eps"])
    y = y.reshape(t, inner) * lw["gate_norm"].astype(jnp.float32)
    return _mm(y, lw["w_out"], lower)


def _attention(h, lw, s, lower):
    t, n, nkv, hd = h.shape[0], s["heads"], s["kv_heads"], s["hd"]
    positions = jnp.arange(t)
    q = _mm(h, lw["wq"], lower).reshape(t, n, hd)
    k = _mm(h, lw["wk"], lower).reshape(t, nkv, hd)
    v = _mm(h, lw["wv"], lower).reshape(t, nkv, hd)
    causal = positions[None, :] <= positions[:, None]
    # query head i reads kv head i // (n / nkv); no position signal
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))

    def head(args):
        qh, kh, vh = args                       # [T, d] of one head
        score = jnp.matmul(qh, kh.T, precision=HIGHEST) * hd ** -0.5
        score = jnp.where(causal, score, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(score, -1), vh, precision=HIGHEST)

    per_head = lambda a: jnp.moveaxis(a, 1, 0)
    o = jax.lax.map(head, (per_head(q), per_head(k), per_head(v)))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(t, n * hd), lw["wo"], lower)


def _relu2(h, up, down, lower):
    return _mm(jnp.square(jax.nn.relu(_mm(h, up, lower))), down, lower)


def _experts(h, lw, s, lower):
    t = h.shape[0]
    score = _scores(h, lw, lower)
    chosen = jax.lax.top_k(score + lw["router_bias"], s["topk"])[1]  # [T, k]
    picked = jnp.take_along_axis(score, chosen, 1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) * s["scale"]
    # weight of every routed expert for every token, 0 where not chosen
    full = jnp.zeros((t, s["routed"]), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(weight)
    mine = full[:, s["offset"]:s["offset"] + s["held"]]

    def add(total, expert):
        up, down, w = expert            # up [width, hidden], as nn.Linear's
        return total + w[:, None] * _relu2(h, up.T, down, lower), None

    routed, _ = jax.lax.scan(add, jnp.zeros_like(h),
                             (lw["we_up"], lw["we_down"], mine.T))
    return routed + _relu2(h, lw["ws_up"], lw["ws_down"], lower)


@functools.partial(jax.jit,
                   static_argnames=("kind", "shape", "lower", "scores"))
def _layer(x, lw, *, kind, shape, lower, scores=False):
    """One block over a whole sequence x [T, hidden], causal; with ``scores``
    the router's scores [T, E] of an expert block's tokens instead."""
    s = dict(shape)
    h = _rms_norm(x, lw["norm"], s["eps"])
    if scores:
        return _scores(h, lw, lower)
    mixer = {"mamba": _mamba, "experts": _experts, "attention": _attention}
    return x + mixer[kind](h, lw, s, lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, head, *, eps, lower):
    return _mm(_rms_norm(x, final_norm, eps), head, lower)


def logits(weights: dict, sizes, tokens, first: int, count: int,
           lower: bool = False, config=None) -> np.ndarray:
    """Float32 logits [count, vocab] at positions ``first .. first+count-1``
    of the sequence ``tokens``: the scores of the token that FOLLOWS each of
    those positions.  One full causal forward, block by block."""
    s = _shape(sizes, config)
    tokens = np.asarray(tokens, np.int32)
    lower = s["dtype"] if lower else None
    t = len(tokens)
    padded = -(-t // LENGTH_QUANTUM) * LENGTH_QUANTUM
    # trailing padding cannot reach an earlier position: the convolution,
    # the recurrence and the attention mask are causal, experts per token
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    shape = tuple(sorted((k, v) for k, v in s.items()))
    for kind, lw in zip(s["types"], weights["layers"]):
        x = _layer(x, lw, kind=kind, shape=shape, lower=lower)
    out_pad = -(-count // 64) * 64
    rows = np.minimum(np.arange(first, first + out_pad), padded - 1)
    out = _head(x[jnp.asarray(rows)], weights["final_norm"], weights["head"],
                eps=s["eps"], lower=lower)
    return np.asarray(out[:count])
