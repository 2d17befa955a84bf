"""Mamba-2's selective state-space recurrence (state-space duality), in the
two forms a served model needs, which must agree::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S [H, P, N] float32
    y_t = S_t C_t + D x_t

per head ``h`` of ``H``: ``x_t`` [P], one scalar ``A < 0`` and one step
``dt_t >= 0`` a head; ``B_t``, ``C_t`` [N] are shared by the ``H / G`` heads
of a group.  The decay is input-dependent (``dt``) and diagonal (one scalar
a head), the state ``P x N`` a head whatever the length.

* :func:`ssd_chunked` runs a sequence in blocks of :data:`CHUNK` tokens:
  inside a block the outputs are matrix products under a ``[CHUNK, CHUNK]``
  decay mask built from the running sum of ``dt A`` (float32; every
  exponent is a difference ``<= 0``, so nothing overflows however long the
  block or strong the decay), and the state goes from block to block by a
  scan.  It takes the state before the sequence and returns the state after
  its last REAL token, so a prompt may come in pieces.
* :func:`ssd_step` is one token of every slot: the state read once and
  written once.

Both are jitted on their own under names of their own (``ssm_scan``,
``ssm_step``): a compiled program's HLO carries them in the ``op_name`` of
every operation they lower to (``jit(ssm_step)``), which is how
``tests/compute/test_tpu_compile.py`` holds the decode update to ONE fusion
over the state.  The profiler's device events carry the HLO line WITHOUT its
metadata (no scope and no inner jit's name reaches a trace: a chip run of PR
39), so the benchmark's reader finds the update by what it yields, a whole
layer's states (``benchmarks/layer_metrics/ssm_step_roofline.nemotron.py``).

A position that is not real (a bucket's padding, a slot that is not live)
is given ``dt = 0``: its decay is ``exp(0) = 1`` and its input ``0 * x B``,
so the state passes it EXACTLY unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: tokens of a block of the chunked form (the published ``chunk_size``)
CHUNK = 128


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssm_scan(x, dt, A, B, C, D, state0, *, chunk: int):
    """:func:`ssd_chunked` behind its masking: ``dt`` is already 0 at the
    positions that are not real."""
    t, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"{t} tokens are not whole blocks of {q}")
    nc = t // q
    f32 = jnp.float32

    def blocks(a, lead: int):
        """[T, <lead dims>, ...] -> [nc, <lead dims>, q, ...]: groups and
        heads lead, so that the products below are batched over them."""
        return jnp.moveaxis(a.reshape((nc, q) + a.shape[1:]), 1, 1 + lead)

    heads = lambda a: a.reshape((t, g, r) + a.shape[2:])
    xc = blocks(heads(x), 2)                             # [nc, G, r, q, P]
    dtc = blocks(heads(dt), 2)                           # [nc, G, r, q]
    bc, cc = blocks(B, 1), blocks(C, 1)                  # [nc, G, q, N]
    # running sum of dt A inside a block: <= 0 and falling
    cum = jnp.cumsum(dtc * A.reshape(g, r, 1), axis=-1)
    # within a block: token t reads token s <= t under exp(cum_t - cum_s)
    causal = jnp.tril(jnp.ones((q, q), jnp.bool_))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    cb = jnp.einsum("cgtn,cgsn->cgts", cc, bc, preferred_element_type=f32)
    mix = decay * cb[:, :, None] * dtc[..., None, :]     # [nc, G, r, t, s]
    y = jnp.einsum("cgrts,cgrsp->cgrtp", mix.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # what a block's own tokens leave in the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)                # [nc, G, r, q]
    fed = ((to_end * dtc)[..., None] * xc.astype(f32)).astype(x.dtype)
    own = jnp.einsum("cgrsp,cgsn->cgrpn", fed, bc,
                     preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])                        # [nc, G, r]

    def to_next(state, block):
        whole, own = block
        return whole[..., None, None] * state + own, state

    last, before = jax.lax.scan(to_next, state0.reshape(g, r, p, n),
                                (whole, own))
    # what the state before the block adds: decayed to token t, read by C_t
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "cgtn,cgrpn->cgrtp", cc.astype(f32), before)
    y = y + D.reshape(g, r, 1, 1) * xc.astype(f32)
    y = jnp.moveaxis(y, 3, 1).reshape(t, h, p)
    return y.astype(x.dtype), last.reshape(h, p, n)


def ssd_chunked(x, dt, A, B, C, D, state0, length, chunk: int = CHUNK):
    """One sequence through the recurrence in blocks of ``chunk``.

    ``x`` [T, H, P]; ``dt`` [T, H] float32, ``>= 0`` (after its softplus);
    ``A`` [H] float32, ``< 0``; ``B``, ``C`` [T, G, N]; ``D`` [H];
    ``state0`` [H, P, N] float32, the state before the first token;
    ``length`` of the T tokens are real (the others neither decay nor feed
    the state).  T is a whole number of blocks (or one block shorter than
    ``chunk``).  Returns ``(y [T, H, P], state [H, P, N] float32)`` after
    the last real token."""
    real = jnp.arange(x.shape[0]) < length
    dt = jnp.where(real[:, None], dt.astype(jnp.float32), 0.0)
    return ssm_scan(x, dt, A, B, C, D, state0, chunk=chunk)


@jax.jit
def ssm_step(x, dt, A, B, C, D, state):
    """:func:`ssd_step`: one fusion over the state on the chip (the update
    and the read by ``C`` in one pass: a compile for a described v5e gives
    one ``multiply_reduce_fusion`` whose outputs are ``y`` and the state,
    written where it was)."""
    b, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    f32 = jnp.float32
    xf = x.astype(f32).reshape(b, g, r, p)
    dtg = dt.reshape(b, g, r)
    decay = jnp.exp(dtg * A.reshape(g, r))
    state = (decay[..., None, None] * state.reshape(b, g, r, p, n)
             + (dtg[..., None] * xf)[..., None]
             * B.astype(f32)[:, :, None, None, :])
    y = (state * C.astype(f32)[:, :, None, None, :]).sum(-1)
    y = y + D.reshape(g, r, 1) * xf
    return y.reshape(b, h, p).astype(x.dtype), state.reshape(b, h, p, n)


def ssd_step(x, dt, A, B, C, D, state):
    """One token of every slot: ``x`` [B, H, P], ``dt`` [B, H] float32 (0
    for a slot that is not live: its state comes back as it went in), ``B``,
    ``C`` [B, G, N], ``state`` [B, H, P, N] float32.  Returns ``(y [B, H,
    P], state)``; the state is read once and written once."""
    return ssm_step(x, dt.astype(jnp.float32), A, B, C, D, state)
