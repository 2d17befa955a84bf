"""Ulysses-style sequence parallelism: all-to-all head↔sequence swap.

The second context-parallelism scheme next to ring attention
(`ops/ring_attention.py`), trading its N-step neighbour pipeline for two
`lax.all_to_all` collectives (DeepSpeed-Ulysses formulation):

1. activations arrive sequence-sharded ``[B, S/n, H, D]``;
2. an all-to-all redistributes them head-sharded ``[B, S, H/n, D]`` — each
   device now holds the FULL sequence for a slice of heads;
3. attention runs *locally and unmodified* — including the fused flash
   kernel, which ring attention's blockwise exchange cannot use;
4. a second all-to-all restores sequence sharding.

Trade-off vs ring: Ulysses moves ``2 × B·S·H·D/n`` bytes in two dense
all-to-alls (balanced ICI traffic, one latency hop each) and needs
``H_kv % n == 0``; ring moves K/V around a ring in N-1 hops and scales to
any head count.  For GQA models with few KV heads (Llama-3: 8), Ulysses
caps at seq=8 — exactly the sweet spot where its fused-kernel advantage
matters; past that, ring takes over (`ShardingPolicy.seq_scheme`).

The reference orchestrator has no in-framework analog (SURVEY.md §2.8 —
it provisions the fabric; user code brings the parallelism).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dstack_tpu.ops import flash_attention as flash
from dstack_tpu.ops.attention import causal_attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "seq",
) -> jnp.ndarray:
    """Causal GQA attention over sequence-sharded shards.  Call *inside*
    ``shard_map`` with the sequence dim of q/k/v sharded over ``axis_name``.

    q: [B, S/n, Hq, D]; k, v: [B, S/n, Hkv, D] (local shards).  Requires
    ``Hq % n == 0 and Hkv % n == 0``.  Returns [B, S/n, Hq, D].
    """
    n = lax.psum(1, axis_name)
    b, s_local, hq, d = q.shape
    hkv = k.shape[2]
    # all_to_all with tiled=True: splits split_axis into n parts, scatters
    # them over the axis, and concatenates received parts along concat_axis
    # — exactly the head↔seq shard swap.
    swap = partial(lax.all_to_all, axis_name=axis_name,
                   split_axis=2, concat_axis=1, tiled=True)
    qf = swap(q)      # [B, S, Hq/n, D]
    kf = swap(k)      # [B, S, Hkv/n, D]
    vf = swap(v)
    s = qf.shape[1]
    group = hq // hkv  # preserved: heads split n-ways on both q and kv
    if flash.supports(s, d, qf.dtype, group=group):
        out = flash.flash_attention(qf, kf, vf)
    else:
        pos = jnp.arange(s)[None, :]
        out = causal_attention(qf, kf, vf, q_positions=pos, kv_positions=pos)
    # inverse swap: seq back to shards, heads back to full
    return lax.all_to_all(out, axis_name=axis_name,
                          split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    seq_axis: str = "seq",
    batch_axes=("dcn", "data", "fsdp"),
    head_axis: Optional[str] = "tensor",
) -> jnp.ndarray:
    """Mesh wrapper (global shapes): batch over ``batch_axes``, heads over
    ``head_axis`` (tensor parallelism composes — the all-to-all then swaps
    the *remaining* head slice), sequence over ``seq_axis``."""
    spec = P(batch_axes, seq_axis, head_axis, None)
    fn = jax.shard_map(
        partial(ulysses_attention, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def supports(cfg, n_seq: int, n_tensor: int = 1) -> bool:
    """Whether Ulysses fits this model/mesh: every head count must split
    over tensor × seq."""
    if n_seq <= 1:
        return True
    return (cfg.num_kv_heads % (n_seq * n_tensor) == 0
            and cfg.num_heads % (n_seq * n_tensor) == 0)
