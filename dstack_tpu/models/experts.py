"""The routed experts a chip holds, for every model whose router is dropless
(``models/ling_hybrid.py``, ``models/lfm2.py``, ``models/nemotron_h.py``):
the router they share (sigmoid scores, a selection bias, optional groups,
normalised top-k times a scale), the token-expert pairs that fall on the
held experts, sorted by expert, through the repo's grouped product
(``ops/grouped_matmul.py``: a Pallas kernel that streams each touched
expert's matrices once), and the load vector the decode windows hand to the
telemetry.

A model's config says what is held: ``experts_held`` experts from
``expert_offset`` of the router's outputs, ``num_experts_per_tok`` pairs a
token.  The model says what an expert is (:data:`EXPERT_FORMS`): which of
the layer's matrix stacks, and what stands between the two grouped products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dstack_tpu.ops import flash_attention as _fa
from dstack_tpu.ops.grouped_matmul import (
    ROW_TILE,
    grouped_relu2,
    grouped_swiglu,
    row_tiles_visited,
)

#: length of the expert-load vector :func:`expert_load` returns
LOAD_FIELDS = 6
#: an expert's form -> the layer's matrix stacks it is made of, in the order
#: :func:`_held` takes them.  ``swiglu``: ``(silu(x gate) * (x up)) down``;
#: ``relu2``: ``relu(x up^T)^2 down``, no gate matrix, both stacks ``[E,
#: width, hidden]`` (up as ``nn.Linear`` keeps it: a width that is no whole
#: number of 128-lane tiles, 1856, then pads nothing on the chip)
EXPERT_FORMS = {
    "swiglu": ("we_gate", "we_up", "we_down"),
    "relu2": ("we_up", "we_down"),
}


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


def relu2(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


@jax.named_scope("moe_route")
def route(h, router, bias, *, top_k: int, scale: float, eps: float = 0.0,
          n_group: int = 1, topk_group: int = 1):
    """Experts and weights of every token of ``h`` [T, D]: ``(ids [T, k],
    weights [T, k] float32)`` over ALL the router's experts.  Sigmoid scores
    in float32; ``bias`` [E] is added for the SELECTION only; with
    ``n_group > 1`` the experts are chosen inside the ``topk_group`` groups
    whose two best selection scores sum highest; the chosen plain scores
    are normalised (``eps`` in their sum, where the model has one) and
    scaled."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choose = scores + bias
    if n_group > 1:
        t = h.shape[0]
        per_group = scores.shape[1] // n_group
        grouped = choose.reshape(t, n_group, per_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        kept = jax.lax.top_k(group_score, topk_group)[1]          # [T, g]
        keep = jnp.zeros((t, n_group), jnp.bool_).at[
            jnp.arange(t)[:, None], kept].set(True)
        choose = jnp.where(jnp.repeat(keep, per_group, axis=1), choose,
                           -jnp.inf)
    ids = jax.lax.top_k(choose, top_k)[1]
    picked = jnp.take_along_axis(scores, ids, axis=1)
    total = picked.sum(-1, keepdims=True)
    if eps:
        total = total + eps
    return ids, picked / total * scale


@jax.named_scope("moe_experts")
def held_experts(h, ids, weights, lp, cfg, token_mask, form: str = "swiglu"):
    """What this chip's experts add for the tokens routed to them: ``(y
    [T, D], counts [experts_held])``.  The token-expert pairs are sorted by
    expert and go through the grouped product (``ops/grouped_matmul.py``) in
    the model's ``form`` (:data:`EXPERT_FORMS`: gate and up in one call and
    down in a second; or up, relu squared, down); pairs of absent experts
    and of masked tokens sort last, are not computed, come out of the
    product as zeros and carry weight 0.  (The CPU path computes them in
    the last expert's group, so that every row of XLA's product is
    defined.)"""
    return _held(h, ids, weights, tuple(lp[k] for k in EXPERT_FORMS[form]),
                 token_mask, experts=cfg.experts_held,
                 offset=cfg.expert_offset, kernel=_on_chip(), form=form)


def _grouped(rows, matrices, counts, sizes, *, kernel: bool, form: str):
    """The experts' two grouped products over ``rows`` sorted by expert:
    the kernel (``counts``: rows past their sum come out as zeros) or XLA's
    ``ragged_dot`` (``sizes``: every row in some group)."""
    if form == "swiglu":
        we_gate, we_up, we_down = matrices
        if kernel:
            return grouped_swiglu(rows, we_gate, we_up, we_down, counts)
        return jax.lax.ragged_dot(
            jax.nn.silu(jax.lax.ragged_dot(rows, we_gate, sizes))
            * jax.lax.ragged_dot(rows, we_up, sizes), we_down, sizes)
    we_up, we_down = matrices
    if kernel:
        return grouped_relu2(rows, we_up, we_down, counts)
    return jax.lax.ragged_dot(jnp.square(jax.nn.relu(jax.lax.ragged_dot(
        rows, jnp.swapaxes(we_up, 1, 2), sizes))), we_down, sizes)


@functools.partial(jax.jit,
                   static_argnames=("experts", "offset", "kernel", "form"))
def _held(h, ids, weights, matrices, token_mask, *, experts: int,
          offset: int, kernel: bool, form: str):
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
    sizes = None if kernel else counts[:e].at[e - 1].add(counts[e])
    out = _grouped(rows, matrices, counts[:e], sizes, kernel=kernel,
                   form=form)
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
