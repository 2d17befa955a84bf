"""Plain reference of a looped decoder (Ouro's LoopLM, the configuration
``ouro-2.6b``): the dense pre-norm block with a norm on each branch's output
too, the whole stack of layers run ``total_ut_steps`` times over the same
weights, one shared norm at the end of every pass, and an exit gate that
picks the pass whose state the head reads.

    T = total_ut_steps, L layers, theta and eps as the file gives them
    h = E[token]
    for t in 0..T-1:                      # the same L layers' weights every pass
      for l in 0..L-1:
        a = Attn_l(RMSNorm(h; attn_norm_l))     # q,k,v,o without bias; rotate-half
                                                # RoPE over the whole head; causal
        h = h + RMSNorm(a; attn_out_norm_l)     # sandwich: the branch's output too
        m = W_down_l(silu(W_gate_l x) * W_up_l x),  x = RMSNorm(h; mlp_norm_l)
        h = h + RMSNorm(m; mlp_out_norm_l)
      h = RMSNorm(h; final_norm)          # pass t's output s_t AND pass t+1's input
      lam_t = sigmoid(w_gate . s_t + b_gate)
    p_t = lam_t * prod_{j<t}(1 - lam_j) for t < T-1;  p_{T-1} = prod_{j<T-1}(1 - lam_j)
    exit = first t with sum_{j<=t} p_j >= early_exit_threshold, else T-1
    logits = W_head s_exit                # untied head; every pass always runs

Straightforward ``jax.numpy``: float32 everywhere, matrix products at
``highest`` precision, no cache (a pass recomputes its keys and values from
its own input, which is what "a cache layer of its own for (t, l)" stores),
no batching, no kernel, one sequence at a time, and nothing of the program
is imported (the products, the norm and the rotary embedding are
``dense_decoder.py``'s, the reference beside this one).  Departures from
the published modelling code, each also under ``assumed`` in the
configuration file: the weights are random from the seed
(norms ones, the gate's bias 0); the exit is the hard selection above for
every position, as a server decoding one token at a time applies it (the
published training objective weighs all exits; it is not computed here).

``Sizes`` has no place for ``total_ut_steps`` and ``early_exit_threshold``:
they are read from the configuration's own file (``config=`` hands another
one, as the tests' toy does).  The reference upcasts one layer at a time and
keeps one pass's activations, so a float32 copy of the model never exists.

``lower`` computes the same forward in the nearest precision below the one
the configuration states (``dense_decoder.py`` has the rule: the int8 grid
for a bfloat16 model, bfloat16 for a float32 one): the control that the
comparison has to fail.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# the arithmetic every plain reference of a dense block shares: products in
# float32 at ``highest`` (or rounded to the next lower grid first), RMSNorm,
# rotate-half RoPE over the whole head, the padding quantum
from benchmarks.references.dense_decoder import (
    HIGHEST,
    LENGTH_QUANTUM,
    _mm,
    _rms_norm,
    _rope,
)

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "ouro-2.6b.json")


def _loop(config=None) -> tuple:
    """(passes, exit threshold) by the configuration file's published keys."""
    c = config or json.loads(CONFIG_FILE.read_text())
    return int(c["total_ut_steps"]), float(c["early_exit_threshold"])


def init_weights(sizes, seed: int) -> dict:
    """All weights from the seed in ONE jitted call, on the device, in the
    served type, in the tree the program's ``params=`` takes: random normal
    x fan_in ** -0.5, norms ones, the gate's bias 0 (they do not depend on
    the number of passes).  Layers are drawn inside a ``lax.map`` so that
    the float32 temporaries are one layer's."""
    dtype = jnp.dtype(sizes.dtype)
    d, f, v = sizes.hidden, sizes.ffn, sizes.vocab

    def dense(key, shape, fan_in):
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return x.astype(dtype)

    def layer(key):
        k = jax.random.split(key, 7)
        ones = jnp.ones((d,), dtype)
        return {
            "attn_norm": ones, "attn_out_norm": ones,
            "mlp_norm": ones, "mlp_out_norm": ones,
            "wq": dense(k[0], (d, sizes.q_dim), d),
            "wk": dense(k[1], (d, sizes.kv_dim), d),
            "wv": dense(k[2], (d, sizes.kv_dim), d),
            "wo": dense(k[3], (sizes.q_dim, d), sizes.q_dim),
            "w_gate": dense(k[4], (d, f), d),
            "w_up": dense(k[5], (d, f), d),
            "w_down": dense(k[6], (f, d), f),
        }

    def build(key):
        k_embed, k_head, k_gate, k_layers = jax.random.split(key, 4)
        return {
            "embed": dense(k_embed, (v, d), d),
            "layers": jax.lax.map(layer, jax.random.split(k_layers,
                                                          sizes.layers)),
            "final_norm": jnp.ones((d,), dtype),
            "exit_gate": {"w": dense(k_gate, (d,), d),
                          "b": jnp.zeros((1,), dtype)},
            "lm_head": dense(k_head, (d, v), d),
        }

    # the counter-based generator of XLA: several times faster on the chip
    # than the default threefry for billions of values
    key = jax.random.key(int(seed), impl="rbg")
    return jax.jit(build)(key)


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _layer(x, lw, *, sizes, lower):
    """One sandwich block over a whole sequence x [S, hidden], causal."""
    s = x.shape[0]
    positions = jnp.arange(s)
    h = _rms_norm(x, lw["attn_norm"], sizes.rms_eps)
    q = _mm(h, lw["wq"], lower).reshape(s, sizes.heads, sizes.head_dim)
    k = _mm(h, lw["wk"], lower).reshape(s, sizes.kv_heads, sizes.head_dim)
    v = _mm(h, lw["wv"], lower).reshape(s, sizes.kv_heads, sizes.head_dim)
    q = _rope(q, positions, sizes.rope_theta)
    k = _rope(k, positions, sizes.rope_theta)
    group = sizes.heads // sizes.kv_heads
    causal = positions[None, :] <= positions[:, None]

    def one_kv_head(args):
        qh, kh, vh = args                       # [S, group, D], [S, D], [S, D]
        scores = jnp.einsum("tgd,sd->gts", qh, kh, precision=HIGHEST)
        scores = jnp.where(causal[None], scores * sizes.head_dim ** -0.5,
                           -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vh, precision=HIGHEST)

    qg = q.reshape(s, sizes.kv_heads, group, sizes.head_dim)
    attn = jax.lax.map(one_kv_head, (jnp.moveaxis(qg, 1, 0),
                                     jnp.moveaxis(k, 1, 0),
                                     jnp.moveaxis(v, 1, 0)))
    attn = jnp.moveaxis(attn, 0, 1).reshape(s, sizes.q_dim)
    x = x + _rms_norm(_mm(attn, lw["wo"], lower), lw["attn_out_norm"],
                      sizes.rms_eps)
    h = _rms_norm(x, lw["mlp_norm"], sizes.rms_eps)
    gated = jax.nn.silu(_mm(h, lw["w_gate"], lower)) * _mm(h, lw["w_up"],
                                                           lower)
    return x + _rms_norm(_mm(gated, lw["w_down"], lower), lw["mlp_out_norm"],
                         sizes.rms_eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _end_of_pass(x, rows, final_norm, gate_w, gate_b, *, eps):
    """The shared norm over the pass's output, and at ``rows`` the state
    s_t and the gate's lam_t."""
    x = _rms_norm(x, final_norm, eps)
    s = x[rows]
    lam = jax.nn.sigmoid(jnp.matmul(s, gate_w.astype(jnp.float32),
                                    precision=HIGHEST)
                         + gate_b.astype(jnp.float32)[0])
    return x, s, lam


def exit_steps(lams, threshold: float) -> np.ndarray:
    """The pass each position exits at, from its gate values ``lams`` [T,
    n]: the first t whose cumulative exit probability reaches the
    threshold, the last pass taking the remainder."""
    lams = np.asarray(lams, np.float32)
    t_steps = lams.shape[0]
    survive = np.ones_like(lams[0])
    reached = np.zeros_like(lams[0])
    chosen = np.full(lams.shape[1:], t_steps - 1, np.int64)
    done = np.zeros(lams.shape[1:], bool)
    for t in range(t_steps):
        p = survive if t == t_steps - 1 else lams[t] * survive
        reached = reached + p
        hit = (reached >= np.float32(threshold)) & ~done
        chosen[hit] = t
        done |= hit
        survive = survive * (1.0 - lams[t])
    return chosen


@functools.partial(jax.jit, static_argnames=("lower",))
def _head(s, head, *, lower):
    return _mm(s, head, lower)


def logits(weights: dict, sizes, tokens, first: int, count: int,
           lower: bool = False, config=None, exits: bool = False):
    """Float32 logits [count, vocab] at positions ``first .. first+count-1``
    of the sequence ``tokens``: the scores of the token that FOLLOWS each of
    those positions.  One full causal forward, pass by pass, layer by layer.
    ``exits=True`` also returns the pass each of those positions exited at."""
    t_steps, threshold = _loop(config)
    tokens = np.asarray(tokens, np.int32)
    lower = sizes.dtype if lower else None
    n = len(tokens)
    padded = -(-n // LENGTH_QUANTUM) * LENGTH_QUANTUM
    # trailing padding cannot reach an earlier position through a causal mask
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    out_pad = -(-count // 64) * 64
    rows = jnp.asarray(np.minimum(np.arange(first, first + out_pad),
                                  padded - 1))
    gate = weights["exit_gate"]
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    states, lams = [], []
    for _ in range(t_steps):
        for i in range(sizes.layers):
            lw = jax.tree.map(lambda a: a[i], weights["layers"])
            x = _layer(x, lw, sizes=sizes, lower=lower)
        x, s, lam = _end_of_pass(x, rows, weights["final_norm"], gate["w"],
                                 gate["b"], eps=sizes.rms_eps)
        states.append(s)
        lams.append(np.asarray(lam))
    chosen = exit_steps(np.stack(lams), threshold)
    picked = jnp.take_along_axis(
        jnp.stack(states), jnp.asarray(chosen)[None, :, None], axis=0)[0]
    out = np.asarray(_head(picked, weights["lm_head"], lower=lower)[:count])
    return (out, chosen[:count]) if exits else out
