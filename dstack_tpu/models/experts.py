"""The routed experts a chip holds, for every model whose router is dropless
(``models/ling_hybrid.py``, ``models/lfm2.py``): the token-expert pairs that
fall on the held experts, sorted by expert, through the repo's grouped
product (``ops/grouped_matmul.py``: a Pallas kernel that streams each touched
expert's matrices once), and the load vector the decode windows hand to the
telemetry.

A model's config says what is held: ``experts_held`` experts from
``expert_offset`` of the router's outputs, ``num_experts_per_tok`` pairs a
token.  The router is the model's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dstack_tpu.ops import flash_attention as _fa
from dstack_tpu.ops.grouped_matmul import (
    ROW_TILE,
    grouped_swiglu,
    row_tiles_visited,
)

#: length of the expert-load vector :func:`expert_load` returns
LOAD_FIELDS = 6


def _on_chip() -> bool:
    """Whether the grouped product is the kernel.  Every backend but the
    CPU's runs it; the CPU backend (the tests' toy models) keeps XLA's
    ``ragged_dot``, as ``serving/paged_window.py`` keeps the XLA gather
    there: interpreted, the kernel costs the model tests half their time
    again, and ``tests/compute/test_grouped_matmul.py`` holds it (and this
    function's two sides against each other) at toy sizes."""
    return not _fa._interpret()


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.named_scope("moe_experts")
def held_experts(h, ids, weights, lp, cfg, token_mask):
    """What this chip's experts add for the tokens routed to them: ``(y
    [T, D], counts [experts_held])``.  The token-expert pairs are sorted by
    expert and go through the grouped product (``ops/grouped_matmul.py``:
    gate and up in one call, down in a second); pairs of absent experts and
    of masked tokens sort last, are not computed, come out of the product
    as zeros and carry weight 0.  (The CPU path computes them in the last
    expert's group, so that every row of XLA's product is defined.)"""
    return _held(h, ids, weights, lp["we_gate"], lp["we_up"], lp["we_down"],
                 token_mask, experts=cfg.experts_held,
                 offset=cfg.expert_offset, kernel=_on_chip())


@functools.partial(jax.jit, static_argnames=("experts", "offset", "kernel"))
def _held(h, ids, weights, we_gate, we_up, we_down, token_mask, *,
          experts: int, offset: int, kernel: bool):
    """:func:`held_experts`, jitted on its own: a program that calls it once
    a layer traces it once and lowers it, its two kernels with it, once for
    all its layers (the Mosaic lowering of a kernel call is tens of
    milliseconds, a cell builds 84 programs of 8 expert layers at start-up,
    and ``setup_s`` is rented time).  ``kernel`` is what ``_on_chip()``
    said: the grouped product is the kernel, else XLA's ``ragged_dot`` (the
    CPU backend's; only the tests' toy models run it)."""
    t, k = ids.shape
    e = experts
    local = ids - offset
    here = (local >= 0) & (local < e)
    if token_mask is not None:
        here = here & token_mask[:, None]
    key = jnp.where(here, local, e).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)
    rows = h[order // k]                                     # [T*k, D]
    if kernel:
        out = grouped_swiglu(rows, we_gate, we_up, we_down, counts[:e])
    else:
        sizes = counts[:e].at[e - 1].add(counts[e])
        out = jax.lax.ragged_dot(
            jax.nn.silu(jax.lax.ragged_dot(rows, we_gate, sizes))
            * jax.lax.ragged_dot(rows, we_up, sizes), we_down, sizes)
    # back to [T, k] by the inverse permutation (a gather, not a scatter-add)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    out = out[inverse].reshape(t, k, -1)
    w = jnp.where(here, weights, 0.0)
    y = jnp.einsum("tkd,tk->td", out, w, preferred_element_type=jnp.float32)
    return y.astype(h.dtype), counts[:e]


def expert_load(counts, tokens, cfg):
    """The load of one expert layer over ``tokens`` unmasked tokens, from
    :func:`held_experts`'s ``counts``: float32 [held pairs, absent pairs,
    largest count of one held expert, mean count of a held expert, held
    experts with a pair, rows the chip's grouped product computes for these
    counts (the row tiles its experts' rows reach into x the tile's rows:
    over the held pairs, what the product pads)]."""
    return _load(counts, tokens, pairs=cfg.num_experts_per_tok,
                 experts=cfg.experts_held)


@functools.partial(jax.jit, static_argnames=("pairs", "experts"))
def _load(counts, tokens, *, pairs: int, experts: int):
    """:func:`expert_load`, jitted on its own for the reason :func:`_held`
    is."""
    held = counts.sum().astype(jnp.float32)
    return jnp.stack([held, tokens * pairs - held,
                      counts.max().astype(jnp.float32), held / experts,
                      (counts > 0).sum().astype(jnp.float32),
                      (row_tiles_visited(counts) * ROW_TILE).astype(
                          jnp.float32)])
