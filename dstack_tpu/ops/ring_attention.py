"""Ring attention: causal attention with the sequence dim sharded over a mesh
axis (context parallelism for long sequences).

Each shard holds a [B, S/n, H, D] slice of Q/K/V.  K/V blocks rotate around
the ``seq`` ring with ``lax.ppermute`` (ICI neighbour exchange on a TPU
slice) while each device folds incoming blocks into an online-softmax
accumulator — attention memory stays O(S/n * S/n) per device and the
block matmuls stay MXU-shaped.

This is the TPU-native answer to long-context scale-out; the reference
(an orchestrator) has no in-framework analog — it only provisions the
cluster fabric (SURVEY.md §2.8).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = jnp.float32(-1e30)


def _block_attn(qg, k, v, q_pos, kv_pos):
    """Partial attention for one KV block.

    qg: [B, Sq, Hkv, G, D] (pre-scaled); k, v: [B, Skv, Hkv, D].
    Returns (m, l, o): block max [B,Hkv,G,Sq], sum of exp, and unnormalized
    output [B, Sq, Hkv, G, D] — all float32.
    """
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    mask = q_pos[:, None, None, :, None] >= kv_pos[:, None, None, None, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)  # [B, Hkv, G, Sq]
    p = jnp.exp(scores - m[..., None])
    # Fully-masked rows: m == -1e30 -> p == 1 for every entry; zero them.
    p = jnp.where((m > 0.5 * _NEG_INF)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m, l, o


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "seq",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal GQA ring attention.  Call *inside* ``shard_map`` with the
    sequence dim of q/k/v sharded over ``axis_name``.

    q: [B, S/n, Hq, D]; k, v: [B, S/n, Hkv, D] (local shards).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    qg = (q * scale).astype(jnp.float32).reshape(b, sq, hkv, hq // hkv, d)
    q_pos = (my_idx * sq + jnp.arange(sq))[None, :].repeat(b, axis=0)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(state, i, k_cur, v_cur):
        m, l, acc = state
        src = (my_idx - i) % n  # whose block we currently hold
        kv_pos = (src * skv + jnp.arange(skv))[None, :].repeat(b, axis=0)
        bm, bl, bo = _block_attn(qg, k_cur, v_cur, q_pos, kv_pos)
        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)  # rescale old accumulator
        beta = jnp.exp(bm - new_m)  # rescale block contribution
        l = l * alpha + bl * beta
        acc = acc * alpha[..., None].transpose(0, 3, 1, 2, 4) + \
            bo * beta[..., None].transpose(0, 3, 1, 2, 4)
        return new_m, l, acc

    def body(i, carry):
        state, k_cur, v_cur = carry
        # Rotate first (n-1 rotations total — the own block was folded in
        # before the loop, and the last-held block needs no onward send);
        # XLA overlaps the ppermute with this step's block compute.
        k_nxt = lax.ppermute(k_cur, axis_name, perm=perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm=perm)
        state = accumulate(state, i, k_nxt, v_nxt)
        return state, k_nxt, v_nxt

    m0 = jnp.full((b, hkv, hq // hkv, sq), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, hq // hkv, sq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, hq // hkv, d), dtype=jnp.float32)
    state = accumulate((m0, l0, acc0), 0, k, v)
    (m, l, acc), _, _ = lax.fori_loop(1, n, body, (state, k, v))

    l = jnp.maximum(l, 1e-30)  # guard rows with no visible keys
    out = acc / l[..., None].transpose(0, 3, 1, 2, 4)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def ring_attention_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    seq_axis: str = "seq",
    batch_axes=("dcn", "data", "fsdp"),
    head_axis: str = "tensor",
) -> jnp.ndarray:
    """Convenience wrapper: shard_map ring attention over a mesh.

    Global shapes; batch sharded over ``batch_axes``, heads over
    ``head_axis``, sequence over ``seq_axis``.
    """
    spec = P(batch_axes, seq_axis, head_axis, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
