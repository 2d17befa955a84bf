"""The routed experts a chip holds, for every model whose router is dropless
(``models/ling_hybrid.py``, ``models/lfm2.py``): the token-expert pairs that
fall on the held experts, sorted by expert, through one grouped product a
matrix, and the load vector the decode windows hand to the telemetry.

A model's config says what is held: ``experts_held`` experts from
``expert_offset`` of the router's outputs, ``num_experts_per_tok`` pairs a
token.  The router is the model's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: length of the expert-load vector :func:`expert_load` returns
LOAD_FIELDS = 5


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.named_scope("moe_experts")
def held_experts(h, ids, weights, lp, cfg, token_mask):
    """What this chip's experts add for the tokens routed to them: ``(y
    [T, D], counts [experts_held])``.  The token-expert pairs are sorted by
    expert and go through one grouped product a matrix; pairs of absent
    experts and of masked tokens sort last, are computed in the last
    expert's group (so that every row of the product is defined) and carry
    weight 0."""
    t, k = ids.shape
    e = cfg.experts_held
    local = ids - cfg.expert_offset
    here = (local >= 0) & (local < e)
    if token_mask is not None:
        here = here & token_mask[:, None]
    key = jnp.where(here, local, e).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)
    group_sizes = counts[:e].at[e - 1].add(counts[e])
    rows = h[order // k]                                     # [T*k, D]
    gated = (jax.nn.silu(jax.lax.ragged_dot(rows, lp["we_gate"], group_sizes))
             * jax.lax.ragged_dot(rows, lp["we_up"], group_sizes))
    out = jax.lax.ragged_dot(gated, lp["we_down"], group_sizes)
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
    experts with a pair]."""
    held = counts.sum().astype(jnp.float32)
    return jnp.stack([held, tokens * cfg.num_experts_per_tok - held,
                      counts.max().astype(jnp.float32),
                      held / cfg.experts_held,
                      (counts > 0).sum().astype(jnp.float32)])
