"""What the providers' programs share (``serving/dense.py``,
``serving/hybrid.py``, ``serving/lfm2.py``): the page arithmetic of a paged
pool leaf [cache layers, blocks, block rows, lanes] under the engine's block
tables, and the decode window's attention over (cache ⧺ window buffer).

A pool leaf is addressed by FLAT row, ``(layer * blocks + block) * rows +
offset`` (``ops/pool.py`` ``scatter_rows`` says why); block 0 is the NULL
block, where every masked write lands.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def paged_kernel_default() -> bool:
    """Whether paged decode attention should run the Pallas block-table
    kernel (ops/flash_attention.py paged_decode_attention) instead of the
    XLA gather path.  ``DSTACK_TPU_PAGED_ATTN_KERNEL``: "auto" (default —
    on for a real TPU backend, off for CPU/interpret where the XLA path
    wins), "1"/"0" to force.  Whichever is chosen is the only path: a
    kernel the compiler refuses fails the decode, nothing falls back."""
    v = os.environ.get("DSTACK_TPU_PAGED_ATTN_KERNEL", "auto")
    if v == "auto":
        return jax.default_backend() == "tpu"
    return v not in ("0", "false", "off")


@jax.named_scope("attn")
def masked_attention(q, k, v, q_pos, kv_pos):
    """Causal GQA attention with explicit position masks (prefill)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, s, hkv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / (d ** 0.5)
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None])[:, None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, s, hq, d)


def slot_rows(rec, slot):
    """One slot's part of a per-slot state tree [layers, slots, ...]."""
    return jax.tree.map(lambda a: a[:, slot], rec)


# -- a chunk of a prompt ------------------------------------------------------

def chunk_pages(prefix_len, cbucket: int, tables_row, block_size: int,
                span: int):
    """Where the ``cbucket`` rows of a chunk behind ``prefix_len`` rows go
    in the slot whose table row is ``tables_row``: ``(block [cbucket],
    offset [cbucket])``.  Padding rows past the slot's span go to the NULL
    block."""
    idx = prefix_len + jnp.arange(cbucket)
    safe = idx < span
    blk = jnp.where(
        safe,
        tables_row[jnp.clip(idx // block_size, 0, span // block_size - 1)],
        0)
    return blk, idx % block_size


def flat_rows(layer, blk, off, num_blocks: int, block_size: int):
    """Flat row indices of (``layer``, ``blk``, ``off``) in a pool leaf."""
    return (layer * num_blocks + blk) * block_size + off


def slot_span(leaf, layer, tables_row, num_blocks: int, lead: tuple = ()):
    """The rows of one slot's pages in ``layer`` laid end to end:
    ``lead + (span, lanes)`` (what a chunk's queries attend)."""
    rows = leaf.reshape((-1,) + leaf.shape[2:])[layer * num_blocks
                                                 + tables_row]
    return rows.reshape(lead + (-1, leaf.shape[-1]))


# -- a decode window ----------------------------------------------------------

def window_rows(cache_layers: int, base_len, active, tables, win_j,
                block_size: int, num_blocks: int):
    """Flat row indices [cache layers, B, W] of a decode window's rows:
    slot b's row ``win_j[j]`` = j stands at position ``base_len[b] + j`` of
    its pages (``tables`` [B, columns]).  Overshoot past the table's span and
    slots that are not ``active`` (released, or mid-chunked-prefill: their
    window rows are junk and a chunk may be filling those pages) land in the
    NULL block, so the indices are not unique."""
    nbk = tables.shape[1]
    pos = base_len[:, None] + win_j[None, :]                     # [B, W]
    safe = (pos < nbk * block_size) & active[:, None]
    blk_col = jnp.clip(pos // block_size, 0, nbk - 1)
    phys = jnp.where(
        safe, jnp.take_along_axis(tables, blk_col, axis=1), 0)
    off = pos % block_size
    return ((jnp.arange(cache_layers)[:, None, None]
             * num_blocks + phys[None]) * block_size + off[None])


def attend_pages_and_window(paged_attn, qg, pool_k, pool_v, layer, tables,
                            base_len, wk, wv, win_mask, dtype):
    """One decode step's attention with the cache half read in place by the
    block-table kernel (``ops/flash_attention.py``
    ``paged_decode_attention``, or its ``shard_map``): a normalized output
    and a logsumexp per slot over ``base_len`` cache rows of ``layer``,
    merged by logsumexp with the window half, computed here over the
    layer's window slabs ``wk``/``wv`` [W, B, Hkv, D] under ``win_mask``
    [1, 1, 1, W].  ``qg`` [B, Hkv, G, D]; numerically the same attention set
    as :func:`attend_view_and_window`, reduction order aside."""
    scale = qg.shape[-1] ** -0.5
    with jax.named_scope("paged_attn"):
        o_c, lse_c = paged_attn(qg, pool_k, pool_v, layer, tables, base_len)
    with jax.named_scope("attn"):
        s_w = jnp.einsum("bhgd,jbhd->bhgj", qg, wk) * scale
        s_w = jnp.where(win_mask, s_w, -1e30).astype(jnp.float32)
        m_w = jnp.max(s_w, axis=-1)
        p_w = jnp.exp(s_w - m_w[..., None])
        l_w = jnp.sum(p_w, axis=-1)
        o_w = jnp.einsum(
            "bhgj,jbhd->bhgd", p_w.astype(dtype), wv
        ).astype(jnp.float32) / l_w[..., None]
        lse_w = m_w + jnp.log(l_w)
        # empty-cache slots have lse_c = -inf; the window half always has
        # column 0 visible, so lse is finite
        lse = jnp.logaddexp(lse_c, lse_w)
        return (o_c * jnp.exp(lse_c - lse)[..., None]
                + o_w * jnp.exp(lse_w - lse)[..., None]).astype(dtype)


@jax.named_scope("attn")
def attend_view_and_window(qg, lk, lv, cache_mask, wk, wv, win_mask, dtype):
    """One decode step's attention over a linear view of the cache ``lk``/
    ``lv`` [B, S, Hkv, D] (a dense cache's rows, or a slot's gathered
    pages) under ``cache_mask`` [B, 1, 1, S], and the window slabs: one
    softmax over both."""
    scale = qg.shape[-1] ** -0.5
    kv_span = lk.shape[1]
    s_c = jnp.einsum("bhgd,bkhd->bhgk", qg, lk) * scale
    s_c = jnp.where(cache_mask, s_c, -1e30)
    s_w = jnp.einsum("bhgd,jbhd->bhgj", qg, wk) * scale
    s_w = jnp.where(win_mask, s_w, -1e30)
    s = jnp.concatenate([s_c, s_w], axis=-1)
    probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
    p_c, p_w = probs[..., :kv_span], probs[..., kv_span:]
    return (jnp.einsum("bhgk,bkhd->bhgd", p_c, lv)
            + jnp.einsum("bhgj,jbhd->bhgd", p_w, wv))
