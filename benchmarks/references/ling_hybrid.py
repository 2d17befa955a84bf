"""Plain reference of the Ling-3.0-flash language model's block, as the
configuration ``ling-3.0-flash-vl-7l-ep4`` cuts it: pre-norm residual layers
whose mixer is KDA (Kimi Delta Attention, arXiv:2510.26692) or, every sixth,
MLA (DeepSeek-V2's latent attention), and whose feed-forward is a dense
SwiGLU first and then 512-way sigmoid-routed experts beside a shared one.

Straightforward ``jax.numpy``: float32, matrix products at ``highest``, no
cache, no kernel, one sequence at a time, the KDA recurrence token by token,
every held expert computed for every token and masked.  It imports nothing
of the program.  ``Sizes`` carries a dense decoder's numbers only, so the
rest is read from the configuration's own file (``config=`` hands another
one in: the CPU tests run this file at a toy size).  The weights are the
benchmark's own, made here from the seed in the tree layout the program's
``params=`` takes; a layer is upcast when it is used.

The layer equations (each reading of a published key that the catalog does
not explain is listed under ``assumed`` in the configuration file):

KDA, H heads of d_k = d_v = ``head_dim``, no positional encoding::

    q, k, v = SiLU(conv(W_q x)), SiLU(conv(W_k x)), SiLU(conv(W_v x))
    conv: causal, depthwise, over the last ``short_conv_kernel_size`` tokens
    q, k L2-normalised per head; q scaled by d_k ** -0.5
    g = kda_lower_bound * sigmoid(exp(A_log[h]) * (W_f x + dt_bias))
    beta = sigmoid(W_beta x)                              one a head
    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t;  y = W_o (RMSNorm_head(o_t) * sigmoid(W_g x)[h])

MLA, ``q_lora_rank`` null::

    q = W_q x -> H x (d_n + d_r);  [c, k_r] = W_dkv x -> r + d_r
    c = RMSNorm(c);  [k_n, v] = W_ukv c -> H x (d_n + d_v)
    rotate-half RoPE on q's d_r part and on k_r (shared by all heads)
    softmax(q . [k_n, k_r] / sqrt(d_n + d_r)) v;  W_o

Experts::

    s = sigmoid(W_r x) over all routed experts;  chosen on s + bias:
    a group's score is the sum of its top 2, the best ``topk_group`` of
    ``n_group`` groups stay, the top ``num_experts_per_tok`` inside them;
    w_i = s_i / sum_chosen s_j * routed_scaling_factor
    y = sum over chosen experts HELD HERE of w_i E_i(x)  +  E_shared(x)

The chip holds ``num_experts`` of ``num_routed_experts`` experts from
``expert_offset``; what the absent ones would add is left out, here as in
the program.

The selection bias is what training leaves in the published model: the
values under which every expert gets the same share of the tokens
(``moe_router_enable_expert_bias``).  ``init_weights`` has no training run,
so it fits them: ``CALIBRATION`` sequences of uniform token ids from the
seed go through the layers made so far, and each router's bias is moved
against its experts' loads until they are even (``_even_bias``).  A random
bias would make a few experts hot for every token, each draw its own few.

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

HIGHEST = jax.lax.Precision.HIGHEST
LENGTH_QUANTUM = 256
#: sequences and tokens each that ``init_weights`` fits the routers' selection
#: bias on: 64 pairs an expert at 512 experts and 8 a token
CALIBRATION = (16, 256)
#: passes of ``_even_bias``, each moving the bias by RATE * DECAY ** pass *
#: log(load / even load)
BALANCE_PASSES, BALANCE_RATE, BALANCE_DECAY = 40, 0.03, 0.95
CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "ling-3.0-flash-vl-7l-ep4.json")


def _shape(sizes, config=None) -> dict:
    """What ``Sizes`` lacks, by the configuration file's published keys."""
    c = config or json.loads(CONFIG_FILE.read_text())
    return {
        "d": sizes.hidden, "ffn": sizes.ffn, "heads": sizes.heads,
        "vocab": sizes.vocab, "eps": sizes.rms_eps, "theta": sizes.rope_theta,
        "dtype": sizes.dtype, "types": tuple(c["layer_types"]),
        "dense": c["first_k_dense_replace"], "hd": c["head_dim"],
        "conv": c["short_conv_kernel_size"], "bound": c["kda_lower_bound"],
        "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
        "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
        "routed": c["num_routed_experts"], "held": c["num_experts"],
        "offset": c["expert_offset"], "topk": c["num_experts_per_tok"],
        "groups": c["n_group"], "keep": c["topk_group"],
        "scale": c["routed_scaling_factor"],
        "f_expert": c["moe_intermediate_size"],
        "f_shared": c["moe_shared_expert_intermediate_size"],
    }


def init_weights(sizes, seed: int, config=None) -> dict:
    """All weights from the seed, on the device, in the served type: one
    jitted call a layer, so that the float32 temporaries are one layer's.
    The routers' selection bias is fitted last (``_fit_selection_bias``)."""
    s = _shape(sizes, config)
    dtype = jnp.dtype(s["dtype"])
    d, h = s["d"], s["heads"]

    def dense(key, shape, fan_in, scale=1.0):
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return (x * scale).astype(dtype)

    def small(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    def layer(key, kind, dense_ffn):
        k = jax.random.split(key, 20)
        lw = {"attn_norm": jnp.ones((d,), dtype),
              "mlp_norm": jnp.ones((d,), dtype)}
        if kind == "kda":
            c = h * s["hd"]
            lw.update(
                w_qkv=dense(k[0], (d, 3 * c), d),
                conv_w=dense(k[1], (s["conv"], 3 * c), s["conv"]),
                w_f=dense(k[2], (d, c), d), a_log=small(k[3], (h,), 0.5),
                dt_bias=small(k[4], (c,), 1.0),
                w_beta=dense(k[5], (d, h), d), w_g=dense(k[6], (d, h), d),
                o_norm=jnp.ones((s["hd"],), dtype), wo=dense(k[7], (c, d), c))
        else:
            lw.update(
                wq=dense(k[0], (d, h * (s["dn"] + s["dr"])), d),
                w_dkv=dense(k[1], (d, s["r"] + s["dr"]), d),
                kv_norm=jnp.ones((s["r"],), dtype),
                w_ukv=dense(k[2], (s["r"], h * (s["dn"] + s["dv"])), s["r"]),
                wo=dense(k[3], (h * s["dv"], d), h * s["dv"]))
        if dense_ffn:
            f = s["ffn"]
            lw.update(w_gate=dense(k[10], (d, f), d),
                      w_up=dense(k[11], (d, f), d),
                      w_down=dense(k[12], (f, d), f))
        else:
            e, f, fs = s["held"], s["f_expert"], s["f_shared"]
            lw.update(
                router=dense(k[10], (d, s["routed"]), d),
                router_bias=jnp.zeros((s["routed"],), jnp.float32),
                we_gate=dense(k[12], (e, d, f), d),
                we_up=dense(k[13], (e, d, f), d),
                we_down=dense(k[14], (e, f, d), f),
                ws_gate=dense(k[15], (d, fs), d),
                ws_up=dense(k[16], (d, fs), d),
                ws_down=dense(k[17], (fs, d), fs))
        return lw

    def ends(key):
        k_embed, k_head = jax.random.split(key)
        return {"embed": dense(k_embed, (s["vocab"], d), d),
                "lm_head": dense(k_head, (d, s["vocab"]), d),
                "final_norm": jnp.ones((d,), dtype)}

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


def _int8_grid(x, axis):
    """Round to 255 symmetric levels scaled by the largest magnitude along
    ``axis`` (what an int8 matrix unit would be fed), kept in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, lower):
    """x @ w in float32 at ``highest``; ``lower`` names the stated type whose
    next lower precision the operands are rounded to first."""
    w = w.astype(jnp.float32)
    if lower == "float32":
        x, w = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (x, w))
    elif lower:
        x, w = _int8_grid(x, -1), _int8_grid(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotate-half rotary embedding over the whole last dim of x [T, ...,
    d]."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _kda(h, lw, s, lower):
    t, n, hd = h.shape[0], s["heads"], s["hd"]
    proj = _mm(h, lw["w_qkv"], lower)
    taps = lw["conv_w"].astype(jnp.float32)
    # token t sees itself under the LAST tap and t - j under the j-th before
    past = jnp.concatenate(
        [jnp.zeros((s["conv"] - 1, proj.shape[1]), jnp.float32), proj])
    mixed = sum(taps[j] * past[j:j + t] for j in range(s["conv"]))
    q, k, v = (a.reshape(t, n, hd)
               for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k = unit(q) * hd ** -0.5, unit(k)
    gate_in = _mm(h, lw["w_f"], lower) + lw["dt_bias"]
    g = s["bound"] * jax.nn.sigmoid(
        jnp.exp(lw["a_log"])[:, None] * gate_in.reshape(t, n, hd))
    beta = jax.nn.sigmoid(_mm(h, lw["w_beta"], lower))

    def token(state, inputs):
        qt, kt, vt, gt, bt = inputs             # [H, d], beta [H]
        decayed = jnp.exp(gt)[:, :, None] * state
        err = vt - jnp.einsum("hkv,hk->hv", decayed, kt, precision=HIGHEST)
        state = decayed + jnp.einsum("hk,hv->hkv", bt[:, None] * kt, err,
                                     precision=HIGHEST)
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((n, hd, hd), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms_norm(o, lw["o_norm"], s["eps"])
    o = o * jax.nn.sigmoid(_mm(h, lw["w_g"], lower))[:, :, None]
    return _mm(o.reshape(t, n * hd), lw["wo"], lower)


def _mla(h, lw, s, lower):
    t, n = h.shape[0], s["heads"]
    dn, dr, dv, r = s["dn"], s["dr"], s["dv"], s["r"]
    positions = jnp.arange(t)
    q = _mm(h, lw["wq"], lower).reshape(t, n, dn + dr)
    down = _mm(h, lw["w_dkv"], lower)
    c = _rms_norm(down[:, :r], lw["kv_norm"], s["eps"])
    k_rope = _rope(down[:, r:], positions, s["theta"])
    up = _mm(c, lw["w_ukv"], lower).reshape(t, n, dn + dv)
    q_rope = _rope(q[..., dn:], positions, s["theta"])
    causal = positions[None, :] <= positions[:, None]

    def head(args):
        qn, qr, kn, vh = args                   # [T, .] of one head
        score = (jnp.matmul(qn, kn.T, precision=HIGHEST)
                 + jnp.matmul(qr, k_rope.T, precision=HIGHEST))
        score = jnp.where(causal, score * (dn + dr) ** -0.5, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(score, -1), vh, precision=HIGHEST)

    per_head = lambda a: jnp.moveaxis(a, 1, 0)
    o = jax.lax.map(head, (per_head(q[..., :dn]), per_head(q_rope),
                           per_head(up[..., :dn]), per_head(up[..., dn:])))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(t, n * dv), lw["wo"], lower)


def _swiglu(h, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(h, gate, lower)) * _mm(h, up, lower), down,
               lower)


def _chosen(choose, s):
    """The experts [T, k] each token picks by its selection scores ``choose``
    [T, E]: the best ``keep`` groups by the sum of their top 2, then the top
    ``topk`` inside them."""
    t = choose.shape[0]
    per = s["routed"] // s["groups"]
    in_groups = choose.reshape(t, s["groups"], per)
    group_score = jax.lax.top_k(in_groups, 2)[0].sum(-1)
    kept = jax.lax.top_k(group_score, s["keep"])[1]
    stays = (jnp.arange(s["groups"])[None, :, None] == kept[:, None, :]).any(-1)
    choose = jnp.where(jnp.repeat(stays, per, 1), choose, -jnp.inf)
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


def _scores(h, lw, lower):
    return jax.nn.sigmoid(_mm(h, lw["router"], lower))           # [T, E]


def _experts(h, lw, s, lower):
    t = h.shape[0]
    score = _scores(h, lw, lower)
    chosen = _chosen(score + lw["router_bias"], s)               # [T, k]
    picked = jnp.take_along_axis(score, chosen, 1)
    weight = picked / picked.sum(-1, keepdims=True) * s["scale"]
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
    return routed + _swiglu(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"],
                            lower)


@functools.partial(jax.jit,
                   static_argnames=("kind", "shape", "lower", "scores"))
def _layer(x, lw, *, kind, shape, lower, scores=False):
    """One block over a whole sequence x [T, hidden], causal; with ``scores``
    the router's scores [T, E] of the block's tokens instead."""
    s = dict(shape)
    h = _rms_norm(x, lw["attn_norm"], s["eps"])
    x = x + (_kda if kind == "kda" else _mla)(h, lw, s, lower)
    h = _rms_norm(x, lw["mlp_norm"], s["eps"])
    if scores:
        return _scores(h, lw, lower)
    if "router" in lw:
        return x + _experts(h, lw, s, lower)
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, head, *, eps, lower):
    return _mm(_rms_norm(x, final_norm, eps), head, lower)


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
    out = _head(x[jnp.asarray(rows)], weights["final_norm"],
                weights["lm_head"], eps=s["eps"], lower=lower)
    return np.asarray(out[:count])
