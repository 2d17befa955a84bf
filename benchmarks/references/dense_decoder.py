"""Plain reference of a dense pre-norm decoder (the Llama / Mistral / SmolLM2
block): RMSNorm, rotary attention with grouped KV heads, SwiGLU, an output
head that is either its own matrix or the embedding transposed.

It follows the published equations in straightforward ``jax.numpy``:
float32 everywhere, matrix products at ``highest`` precision, no cache, no
batching, no kernel, one sequence at a time.  It imports nothing of the
program.  The weights are the benchmark's own, made here from the seed in
the tree layout the program's ``params=`` argument takes, in the type the
configuration states; the reference upcasts one layer at a time so that a
float32 copy of the whole model never exists.

``lower`` computes the same forward in the nearest precision below the one
the configuration states, the step that would tempt a later PR: for a
bfloat16 (or float16) model every matrix product's two operands are rounded
to the int8 grid (per row of the activations, per output column of the
weights); for a float32 model they are rounded to bfloat16.  It is the
control that the comparison has to fail; the benchmark's own runs never
call it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.sizes import Sizes

HIGHEST = jax.lax.Precision.HIGHEST
#: sequence lengths are padded up to a multiple of this, so that requests of
#: different lengths share a handful of compiled layer programs
LENGTH_QUANTUM = 256


def init_weights(sizes: Sizes, seed: int) -> dict:
    """All weights from the seed in ONE jitted call, on the device, in the
    served type.  Layers are drawn inside a ``lax.map`` so that the float32
    temporaries are one layer's, not the model's."""
    dtype = jnp.dtype(sizes.dtype)
    d, f, v = sizes.hidden, sizes.ffn, sizes.vocab

    def dense(key, shape, fan_in):
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return x.astype(dtype)

    def layer(key):
        k = jax.random.split(key, 7)
        return {
            "attn_norm": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, sizes.q_dim), d),
            "wk": dense(k[1], (d, sizes.kv_dim), d),
            "wv": dense(k[2], (d, sizes.kv_dim), d),
            "wo": dense(k[3], (sizes.q_dim, d), sizes.q_dim),
            "mlp_norm": jnp.ones((d,), dtype),
            "w_gate": dense(k[4], (d, f), d),
            "w_up": dense(k[5], (d, f), d),
            "w_down": dense(k[6], (f, d), f),
        }

    def build(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)
        tree = {
            "embed": dense(k_embed, (v, d), d),
            "layers": jax.lax.map(layer, jax.random.split(k_layers,
                                                          sizes.layers)),
            "final_norm": jnp.ones((d,), dtype),
        }
        if not sizes.tied:
            tree["lm_head"] = dense(k_head, (d, v), d)
        return tree

    # the counter-based generator of XLA: several times faster on the chip
    # than the default threefry for billions of values
    key = jax.random.key(int(seed), impl="rbg")
    return jax.jit(build)(key)


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
    """Rotate-half rotary embedding, as the published modelling code of
    these families applies it: x is [T, heads, head_dim]."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _layer(x, lw, *, sizes: Sizes, lower):
    """One block over a whole sequence x [T, hidden], causal."""
    t = x.shape[0]
    positions = jnp.arange(t)
    h = _rms_norm(x, lw["attn_norm"], sizes.rms_eps)
    q = _mm(h, lw["wq"], lower).reshape(t, sizes.heads, sizes.head_dim)
    k = _mm(h, lw["wk"], lower).reshape(t, sizes.kv_heads, sizes.head_dim)
    v = _mm(h, lw["wv"], lower).reshape(t, sizes.kv_heads, sizes.head_dim)
    q = _rope(q, positions, sizes.rope_theta)
    k = _rope(k, positions, sizes.rope_theta)
    group = sizes.heads // sizes.kv_heads
    causal = positions[None, :] <= positions[:, None]

    def one_kv_head(args):
        qh, kh, vh = args                       # [T, group, D], [T, D], [T, D]
        s = jnp.einsum("tgd,sd->gts", qh, kh, precision=HIGHEST)
        s = jnp.where(causal[None], s * sizes.head_dim ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vh, precision=HIGHEST)

    qg = q.reshape(t, sizes.kv_heads, group, sizes.head_dim)
    attn = jax.lax.map(one_kv_head, (jnp.moveaxis(qg, 1, 0),
                                     jnp.moveaxis(k, 1, 0),
                                     jnp.moveaxis(v, 1, 0)))
    attn = jnp.moveaxis(attn, 0, 1).reshape(t, sizes.q_dim)
    x = x + _mm(attn, lw["wo"], lower)
    h = _rms_norm(x, lw["mlp_norm"], sizes.rms_eps)
    gated = jax.nn.silu(_mm(h, lw["w_gate"], lower)) * _mm(h, lw["w_up"],
                                                           lower)
    return x + _mm(gated, lw["w_down"], lower)


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _head(x, final_norm, head, *, sizes: Sizes, lower):
    return _mm(_rms_norm(x, final_norm, sizes.rms_eps), head, lower)


def logits(weights: dict, sizes: Sizes, tokens, first: int, count: int,
           lower: bool = False) -> np.ndarray:
    """Float32 logits [count, vocab] at positions ``first .. first+count-1``
    of the sequence ``tokens``: the scores of the token that FOLLOWS each of
    those positions.  One full causal forward, layer by layer."""
    tokens = np.asarray(tokens, np.int32)
    lower = sizes.dtype if lower else None
    t = len(tokens)
    padded = -(-t // LENGTH_QUANTUM) * LENGTH_QUANTUM
    # trailing padding cannot reach an earlier position through a causal mask
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(sizes.layers):
        lw = jax.tree.map(lambda a: a[i], weights["layers"])
        x = _layer(x, lw, sizes=sizes, lower=lower)
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"].T
    out_pad = -(-count // 64) * 64
    rows = np.minimum(np.arange(first, first + out_pad), padded - 1)
    out = _head(x[jnp.asarray(rows)], weights["final_norm"], head,
                sizes=sizes, lower=lower)
    return np.asarray(out[:count])
