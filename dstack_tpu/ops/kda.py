"""Kimi Delta Attention (KDA, arXiv:2510.26692): a gated delta rule whose
decay is per CHANNEL of the key.  Per head, with a state ``S`` [d_k, d_v]
kept in float32::

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay.  :func:`kda_step` is that recurrence
for one token of every slot (decode); :func:`kda_chunked` computes the same
outputs and final state for a whole sequence block-parallel (the WY form
over chunks), carrying the state from chunk to chunk (prefill).  A token
with ``g = 0`` and ``beta = 0`` leaves the state as it was, which is how
callers mask padding and idle slots.

Everything here is float32 at ``highest`` precision: the state is read by
every later token, so an error made once stays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64


def l2_normalize(x, eps: float = 1e-6):
    """x / ||x|| over the last dim, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_gate(a, a_log, dt_bias, lower_bound: float):
    """Log-decay per channel from the gate projection ``a`` [..., H, d_k]:
    ``lower_bound * sigmoid(exp(a_log)[h] * (a + dt_bias))``, in
    ``(lower_bound, 0)`` (the lower-bounded "safe" gate)."""
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    a = a.astype(jnp.float32) + dt_bias.astype(jnp.float32).reshape(
        a.shape[-2:])
    return lower_bound * jax.nn.sigmoid(rate * a)


@jax.named_scope("kda")
def kda_step(state, q, k, v, g, beta):
    """One token per row.  ``state`` [..., d_k, d_v] float32; ``q, k, g``
    [..., d_k]; ``v`` [..., d_v]; ``beta`` [...].  Returns ``(o, state)``."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * state
    u = v - jnp.einsum("...kv,...k->...v", decayed, k, precision=HIGHEST)
    state = decayed + (beta[..., None] * k)[..., None] * u[..., None, :]
    o = jnp.einsum("...kv,...k->...v", state, q, precision=HIGHEST)
    return o, state


@jax.named_scope("kda")
def kda_chunked(state, q, k, v, g, beta, chunk: int = CHUNK):
    """A whole sequence.  ``state`` [H, d_k, d_v] float32 is the state before
    the first token; ``q, k, g`` [T, H, d_k], ``v`` [T, H, d_v], ``beta``
    [T, H].  Returns ``(o [T, H, d_v], state after the last token)``.

    Inside a chunk of C tokens, with G_t the running sum of g since the
    chunk's start, the updates ``w_s = beta_s (v_s - S'^T k_s)`` solve the
    unit lower-triangular system ``u_t + sum_{s<t} A_ts beta_s u_s = v_t -
    S_0^T (k_t * exp(G_t))`` with ``A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] -
    G_s[c])``.  Every exponent is a DIFFERENCE ``G_t - G_s`` with ``s <= t``
    and so never positive: ``exp(G_t) * exp(-G_s)`` as two factors would
    overflow float32 after 17 tokens at the gate's bound of -5 a token."""
    t, h, dk = q.shape
    c = min(chunk, t)
    if t % c:
        raise ValueError(f"sequence length {t} is no multiple of {c}")
    n = t // c

    def blocks(a):                    # [T, H, ...] -> [N, H, C, ...]
        a = a.astype(jnp.float32).reshape((n, c) + a.shape[1:])
        return jnp.moveaxis(a, 2, 1)

    lower = jnp.tril(jnp.ones((c, c), jnp.bool_))
    strict = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)

    def one_chunk(s0, inputs):
        qc, kc, vc, gc, bc = inputs   # [H, C, d], beta [H, C]
        cum = jnp.cumsum(gc, axis=1)
        diff = cum[:, :, None, :] - cum[:, None, :, :]        # [H, t, s, d_k]
        decay = jnp.exp(jnp.where(lower[None, :, :, None], diff, -jnp.inf))
        k_decayed = decay * kc[:, None, :, :]
        a = jnp.sum(kc[:, :, None, :] * k_decayed, axis=-1)   # k_t . k_s
        qk = jnp.sum(qc[:, :, None, :] * k_decayed, axis=-1)  # q_t . k_s
        from_start = jnp.exp(cum)
        rhs = vc - jnp.einsum("htk,hkv->htv", kc * from_start, s0,
                              precision=HIGHEST)
        system = (jnp.where(strict[None], a, 0.0) * bc[:, None, :]
                  + jnp.eye(c, dtype=jnp.float32))
        u = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        w = bc[..., None] * u
        o = (jnp.einsum("htk,hkv->htv", qc * from_start, s0,
                        precision=HIGHEST)
             + jnp.einsum("hts,hsv->htv", qk, w, precision=HIGHEST))
        to_end = jnp.exp(cum[:, -1:, :] - cum)
        s1 = (jnp.exp(cum[:, -1, :])[..., None] * s0
              + jnp.einsum("hsk,hsv->hkv", kc * to_end, w,
                           precision=HIGHEST))
        return s1, o

    state, o = jax.lax.scan(
        one_chunk, state.astype(jnp.float32),
        tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 1, 2).reshape(t, h, -1), state
