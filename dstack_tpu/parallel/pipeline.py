"""SPMD pipeline parallelism over a ``stage`` mesh axis.

GPipe-style microbatch pipelining, expressed the TPU-native way: one SPMD
program under ``jax.shard_map`` with *partial* manual axes — only ``stage``
is manual; every other mesh axis (dcn/data/fsdp/expert/seq/tensor) stays
Auto, so GSPMD keeps inserting the FSDP all-gathers and tensor-parallel
collectives *inside* each stage exactly as it does in the unpipelined model.

Layout: the stacked layer weights ``[L, ...]`` are sharded over ``stage`` on
the leading dim (L = num_stages × layers_per_stage), so each stage holds a
contiguous run of layers and the activation hand-off between stages is one
``lax.ppermute`` hop — nearest-neighbour ICI traffic on a real slice (the
scaling-book pipelining recipe; same schedule family as MaxText's circular
pipeline, minus weight circulation).

Schedule: classic fill–drain.  With M microbatches and S stages the loop
runs M+S-1 ticks; each tick every stage applies its local layers to its
in-flight microbatch, the last stage banks its finished microbatch, and
activations rotate one hop.  Bubble fraction is (S-1)/(M+S-1) — callers
pick ``num_microbatches`` ≥ S to amortize (default: S).

The whole schedule lives inside ``lax.scan`` (static trip count, no Python
control flow), so it is jit-compiled once and reverse-differentiable — the
backward pass is the mirrored drain-fill pipeline that autodiff derives
from ppermute/scan transposition; no hand-written backward schedule.

Reference parity: the reference orchestrator has no in-framework pipeline
engine — it delegates to torch (``torchtitan``-style user code) and only
wires up NCCL rendezvous (``runner/internal/runner/executor/executor.go``).
Here pipeline parallelism is a first-class axis of the framework's own
compute stack, alongside fsdp/tensor/seq/expert (`parallel/mesh.py`).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Carry = Any  # activation pytree flowing through the layer stack


def stage_size(mesh: Optional[Mesh], stage_axis: Optional[str]) -> int:
    if mesh is None or not stage_axis:
        return 1
    return mesh.shape.get(stage_axis, 1)


def pipeline_layers(
    layer_fn: Callable[[jnp.ndarray, Any], tuple[jnp.ndarray, Any]],
    layers: Any,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
    num_microbatches: Optional[int] = None,
):
    """Run ``x -> scan(layer_fn, x, layers)`` pipelined over ``stage_axis``.

    ``layer_fn(carry, lp) -> (carry, _)`` is the per-layer body (same
    signature as the ``lax.scan`` the unpipelined model uses; wrap it with
    remat *before* passing).  ``layers`` is the stacked ``[L, ...]`` weight
    pytree whose leading dim is sharded over ``stage_axis``; ``x`` is the
    activation ``[B, ...]`` (batch sharded over the usual batch axes, never
    over ``stage``).

    Constraints: L and the microbatch count must divide evenly (``L %
    num_stages == 0``, ``B % num_microbatches == 0``); under other mesh
    axes, B/num_microbatches must still divide the batch-axis product.
    """
    num_stages = stage_size(mesh, stage_axis)
    if num_stages <= 1:
        out, _ = lax.scan(layer_fn, x, layers)
        return out

    n_layers = jax.tree.leaves(layers)[0].shape[0]
    if n_layers % num_stages:
        raise ValueError(
            f"num_layers={n_layers} not divisible by {num_stages} pipeline "
            f"stages (axis {stage_axis!r})")
    m = num_microbatches or num_stages
    batch = x.shape[0]
    if batch % m:
        raise ValueError(f"batch={batch} not divisible by "
                         f"num_microbatches={m}")

    def body(layers_local, x):
        stage = lax.axis_index(stage_axis)
        xs = x.reshape(m, batch // m, *x.shape[1:])
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        # Partial permutation: no wraparound pair — stage 0 overwrites its
        # buffer with the next microbatch anyway, so shipping the last
        # stage's activation back around (the slowest stage link) would be
        # pure waste; ppermute fills the unsourced stage-0 slot with zeros.
        fwd = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # Stage 0 picks up microbatch t (clamped — the drain ticks reuse
            # the last microbatch's values, which stage 0 then never emits).
            inp = lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, m - 1), 0, keepdims=False)
            buf = jnp.where(stage == 0, inp, buf)
            buf, _ = lax.scan(layer_fn, buf, layers_local)
            # The last stage banks finished microbatch t-(S-1).
            oi = t - (num_stages - 1)
            bank = (stage == num_stages - 1) & (oi >= 0)
            oi = jnp.maximum(oi, 0)
            old = lax.dynamic_index_in_dim(outs, oi, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(bank, buf, old), oi, 0)
            buf = lax.ppermute(buf, stage_axis, fwd)
            return (buf, outs), None

        (_, outs), _ = lax.scan(
            tick, (buf, outs), jnp.arange(m + num_stages - 1))
        # Only the last stage wrote non-zeros; psum replicates the result
        # across the stage axis (out_specs=P() below needs all copies equal).
        outs = lax.psum(outs, stage_axis)
        return outs.reshape(x.shape)

    layer_specs = jax.tree.map(lambda _: P(stage_axis), layers)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(layer_specs, P()),
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )
    return fn(layers, x)
