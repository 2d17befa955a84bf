"""Kimi Delta Attention (KDA, arXiv:2510.26692): a gated delta rule whose
decay is per CHANNEL of the key.  Per head, with a state ``S`` [d_k, d_v]
kept in float32::

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay.  :func:`kda_step` is that recurrence
for one token of every slot (decode); :func:`kda_step_into` is the same step
as a Pallas TPU kernel that reads and writes one layer of a stacked state in
place; :func:`kda_chunked` computes the same outputs and final state for a
whole sequence block-parallel (the WY form over chunks), carrying the state
from chunk to chunk (prefill).  A token with ``g = 0`` and ``beta = 0``
leaves the state as it was, which is how callers mask padding and idle
slots.

Everything here is float32 at ``highest`` precision: the state is read by
every later token, so an error made once stays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dstack_tpu.ops import flash_attention as _fa

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64
#: the step kernel's name in a compiled program and in a profiler trace
KERNEL_NAME = "kda_step"
#: bytes of state one grid step of the kernel reads (and writes), at most
BLOCK_BYTES = 2 << 20


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


def _step_kernel(layer_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref, *,
                 heads: int):
    """:func:`kda_step` for the slots of one block, a [d_k, d_v] tile at a
    time.  ``cols_ref`` [slots, d_k, 4 H] holds ``exp(g) | k | beta k | q``
    of every head as COLUMNS (d_k on sublanes), so that each scales the
    tile's rows; the sums over d_k are float32 sums over sublanes (the VPU,
    no matrix unit)."""
    del layer_ref   # the block's index_map reads it
    for b in range(s_ref.shape[0]):
        cols = cols_ref[b]
        for h in range(heads):
            def column(part):
                return cols[:, part * heads + h:part * heads + h + 1]

            decayed = column(0) * s_ref[b, h]
            u = v_ref[b, h:h + 1, :] - jnp.sum(decayed * column(1), axis=0,
                                               keepdims=True)
            state = decayed + column(2) * u
            s_out_ref[b, h] = state
            o_ref[b, h:h + 1, :] = jnp.sum(state * column(3), axis=0,
                                           keepdims=True)


@jax.named_scope("kda")
def kda_step_into(states, layer: int, q, k, v, g, beta, *,
                  block_bytes: int = BLOCK_BYTES):
    """:func:`kda_step` for layer ``layer`` of a stacked state ``states``
    [L, B, H, d_k, d_v] float32, as one Pallas kernel that reads each
    slot's tiles once and writes them back IN PLACE (the stack is aliased
    to the output; the other layers are not touched); ``q, k, g`` [B, H,
    d_k], ``v`` [B, H, d_v], ``beta`` [B, H].  Returns ``(o [B, H, d_v],
    states)``: the same products in the same order as :func:`kda_step`,
    the output read from the updated tile.  On the CPU backend the kernel
    runs interpreted."""
    return _step_into(states, jnp.full((1,), layer, jnp.int32), q, k, v, g,
                      beta, block_bytes=block_bytes,
                      interpret=_fa._interpret())


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def _step_into(states, layer, q, k, v, g, beta, *, block_bytes: int,
               interpret: bool):
    """:func:`kda_step_into`, jitted on its own with the layer a run-time
    scalar: a program that calls it once a KDA layer traces the kernel once
    and lowers it once for all its layers (``setup_s`` is rented time, as
    ``models/experts.py`` ``_held`` says)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    _, b, h, dk, dv = states.shape
    tile = h * dk * dv * states.dtype.itemsize
    slots = max(d for d in range(1, b + 1)
                if b % d == 0 and (d == 1 or d * tile <= block_bytes))
    cols = jnp.swapaxes(jnp.concatenate(
        [jnp.exp(g), k, beta[..., None] * k, q], axis=1), 1, 2)
    state_block = pl.BlockSpec((None, slots, h, dk, dv),
                               lambda i, layer: (layer[0], i, 0, 0, 0))
    rows = pl.BlockSpec((slots, h, dv), lambda i, layer: (i, 0, 0))
    # both state blocks twice, and room for the tiles in flight
    vmem = 4 * slots * tile + (16 << 20)
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // slots,),
            in_specs=[pl.BlockSpec((slots, dk, 4 * h),
                                   lambda i, layer: (i, 0, 0)),
                      rows, state_block],
            out_specs=[rows, state_block],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=int(vmem)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(layer, cols, v, states)


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
