"""Multi-head latent attention (MLA, DeepSeek-V2) over cached LATENT rows.

The cache holds, per token, the normalised latent ``c`` [r] and the one
rotated key part ``k_r`` [d_r] that all heads share, stored side by side in
a row of ``lanes`` numbers (``r + d_r`` used, the rest zero: a pool row is
kept a multiple of 128 lanes wide).  ``w_ukv`` [r, H, d_n + d_v] expands a
latent into each head's no-position key part and its value.

* :func:`expanded` expands the rows it attends (prefill: many queries
  share the expansion).
* :func:`absorbed` never expands: the query goes through ``W_uk`` into
  latent space, attention weights average the LATENT rows, and ``W_uv``
  maps the average out (decode: one query a slot, the cache read once as
  it lies).  Both compute ``softmax(q.k / sqrt(d_n + d_r)) v``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def latent_rows(c, k_rope, lanes: int):
    """[..., r] and [..., d_r] -> the stored row [..., lanes]."""
    pad = lanes - c.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate(
        [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


@jax.named_scope("mla")
def expanded(q_nope, q_rope, rows, w_ukv, q_pos, kv_pos):
    """``q_nope`` [T, H, d_n], ``q_rope`` [T, H, d_r] (rotated), ``rows``
    [S, lanes] with positions ``kv_pos`` [S]; query t sees rows whose
    position is at most ``q_pos[t]``.  Returns [T, H, d_v]."""
    r, h, _ = w_ukv.shape
    d_n, d_r = q_nope.shape[-1], q_rope.shape[-1]
    kv = jnp.einsum("sr,rhe->she", rows[:, :r], w_ukv)
    k_nope, v = kv[..., :d_n], kv[..., d_n:]
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_rope, rows[:, r:r + d_r]))
    scores = scores.astype(jnp.float32) * (d_n + d_r) ** -0.5
    seen = kv_pos[None, None, :] <= q_pos[None, :, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("hts,shd->thd", probs.astype(v.dtype), v)


@jax.named_scope("mla")
def absorbed(q_nope, q_rope, cache_rows, cache_seen, window_rows,
             window_seen, w_ukv):
    """One query per slot.  ``q_nope`` [B, H, d_n], ``q_rope`` [B, H, d_r];
    ``cache_rows`` [B, S, lanes] (a slot's pages laid end to end) with
    ``cache_seen`` [B, S]; ``window_rows`` [W, B, lanes] (the rows of the
    running decode window, this step's among them) with ``window_seen``
    [W].  Returns [B, H, d_v]."""
    r, h, _ = w_ukv.shape
    d_n, d_r = q_nope.shape[-1], q_rope.shape[-1]
    lanes = cache_rows.shape[-1]
    w_uk, w_uv = w_ukv[..., :d_n], w_ukv[..., d_n:]
    # the query in the stored row's own form, so that the scores are ONE
    # product over whole rows and no slice of the cache is ever taken
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk)
    q_row = latent_rows(q_lat, q_rope, lanes)
    scale = (d_n + d_r) ** -0.5
    s_c = jnp.einsum("bhl,bsl->bhs", q_row, cache_rows).astype(jnp.float32)
    s_w = jnp.einsum("bhl,wbl->bhw", q_row, window_rows).astype(jnp.float32)
    s_c = jnp.where(cache_seen[:, None, :], s_c * scale, -1e30)
    s_w = jnp.where(window_seen[None, None, :], s_w * scale, -1e30)
    probs = jax.nn.softmax(jnp.concatenate([s_c, s_w], axis=-1), axis=-1)
    probs = probs.astype(cache_rows.dtype)
    span = cache_rows.shape[1]
    mean_row = (jnp.einsum("bhs,bsl->bhl", probs[..., :span], cache_rows)
                + jnp.einsum("bhw,wbl->bhl", probs[..., span:], window_rows))
    return jnp.einsum("bhr,rhd->bhd", mean_row[..., :r], w_uv)
