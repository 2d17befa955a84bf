"""Fused causal GQA attention (FlashAttention-2 style) as Pallas TPU kernels.

Why this exists: the XLA path (:func:`dstack_tpu.ops.attention.causal_attention`)
materializes the ``[B, H, Sq, Skv]`` float32 scores tensor in HBM — for the
bench shape (b8 x h32 x s1024) that is ~1 GB per layer per pass, ~3 GB of HBM
traffic per layer counting the softmax round-trips, which dominates the
attention cost on a bandwidth-bound chip.  This kernel streams KV blocks
through VMEM with an online softmax, so scores never touch HBM, and the
backward pass recomputes them blockwise from the saved ``(o, lse)`` pair —
activation memory O(S) instead of O(S^2).

The reference orchestrator has no compute kernels at all (it launches user
containers — see SURVEY.md); this is part of the TPU-native compute path the
rebuilt framework ships alongside the control plane.

Shapes and constraints:
- ``q``: [B, S, Hq, D]; ``k``/``v``: [B, S, Hkv, D]; Hq % Hkv == 0 (GQA).
- Causal masking over contiguous positions 0..S-1 (standard training path;
  packed/offset positions use the XLA path).
- S must be a multiple of the block size (256 by default, shrunk for short
  sequences); whole-sequence rows are held in VMEM per program (see
  :func:`supports`), which caps S at ~8k for D=64 bf16 — long-context goes
  through ring attention (:mod:`dstack_tpu.ops.ring_attention`).

On the CPU backend (the tests' virtual mesh) the kernels run in interpreter
mode; every other backend compiles them, so a device the kernels were not
written for fails at compile time instead of silently interpreting.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os as _os

_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _block_sizes(seq: int) -> tuple[int, int]:
    # read at trace time (not import time) so callers can tune the block
    # size without import-order hazards; 1024 is the measured-best on v5e
    # for the bench shape, and _bwd caps its own VMEM-bound kernel anyway
    bq = min(int(_os.environ.get("DSTACK_TPU_FLASH_BLOCK", "256")), seq)
    while seq % bq:
        bq //= 2
    return bq, bq


def supports(seq: int, head_dim: int, dtype, group: int = 1) -> bool:
    """Whether the fused kernel handles this shape (else use the XLA path).

    The binding constraint is whole-sequence VMEM residency in the merged
    backward program: q + do (input dtype) + the dq output block (input
    dtype) + the f32 dq accumulator scratch — (3*itemsize + 4) bytes per
    (row, lane) — which caps seq at ~8k for d=64 bf16; long-context goes
    through ring attention (:mod:`dstack_tpu.ops.ring_attention`).
    """
    del group  # kept for API stability; no longer affects the budget
    if seq < 128 or seq % 128:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    lanes = max(head_dim, 128)  # lane padding
    per_program = seq * lanes * (3 * itemsize + 4)
    return per_program <= 10 * 1024 * 1024


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, bq, bk):
    iq = pl.program_id(1)
    # inputs stay bf16: bf16 MXU dots with f32 accumulation run ~4x faster
    # than f32 dots on TPU, and f32 score/softmax state keeps the numerics
    q = q_ref[0]  # [BQ, D]
    d = q.shape[-1]

    def body(j, carry, *, masked):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :]
        v = v_ref[0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]
        if masked:
            # only blocks intersecting the diagonal need the causal mask —
            # the iota/compare/select VPU work is a real cost at small D,
            # so fully-visible blocks skip it
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    n_kv = (iq + 1) * bq // bk  # causal: only blocks at/below the diagonal
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    # full blocks (strictly below the diagonal), then the diagonal block(s)
    n_full = iq * bq // bk
    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(body, masked=False), (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(
        n_full, n_kv, functools.partial(body, masked=True), carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [BQ, 1]


def _fwd(q3, k3, v3, scale):
    bh, seq, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    bq, bk = _block_sizes(seq)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(bh, seq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, d), lambda h, i: (h // group, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, d), lambda h, i: (h // group, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_interpret(),
    )(q3, k3, v3)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_merged_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc,
                       *, scale, bq, bk, n_q, n_k):
    """Single-pass backward (unpacked layout): one program per (q head, kv
    block) computes the kv block's dk/dv partials AND accumulates dq into a
    whole-sequence f32 VMEM scratch, flushed on the last kv block.  Shares
    the score/ds recomputation between the dq and dk/dv halves (5 instead of
    7 dots per block pair) and reads q/do once instead of twice; the TPU
    grid is sequential so the scratch persists across jk steps."""
    jk = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    d = k.shape[-1]

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(i, carry, *, masked):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :]
        do = do_ref[0, pl.ds(i * bq, bq), :]
        lse = lse_ref[0, pl.ds(i * bq, bq), :]
        delta = delta_ref[0, pl.ds(i * bq, bq), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p32 = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(
            p32.astype(k.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p32 * (dp - delta)).astype(k.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dq_acc[pl.ds(i * bq, bq), :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    i0 = jk * bk // bq
    i_diag_end = jnp.minimum(((jk + 1) * bk + bq - 1) // bq, n_q)
    dk, dv = jax.lax.fori_loop(
        i0, i_diag_end, functools.partial(body, masked=True), (dk, dv))
    dk, dv = jax.lax.fori_loop(
        i_diag_end, n_q, functools.partial(body, masked=False), (dk, dv))
    dk_ref[0] = dk * scale
    dv_ref[0] = dv

    @pl.when(jk == n_k - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_merged(q3, k3, v3, do3, lse, delta, scale):
    bh, seq, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    bq, bk = _block_sizes(seq)
    bq = min(bq, 512)  # the merged kernel adds a whole-seq f32 scratch;
    bk = min(bk, 512)  # square 1024 blocks exceed scoped VMEM
    return pl.pallas_call(
        functools.partial(_bwd_merged_kernel, scale=scale, bq=bq, bk=bk,
                          n_q=seq // bq, n_k=seq // bk),
        grid=(bh, seq // bk),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, j: (h // group, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, j: (h // group, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, d), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, seq, d), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, j: (h, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, j: (h, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, seq, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32)],
        name="flash_bwd",
        interpret=_interpret(),
    )(q3, k3, v3, do3, lse, delta)


def _bwd(res, do3):
    q3, k3, v3, o3, lse, scale = res
    bh, seq, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [BH, S, 1]
    dq, dk_p, dv_p = _bwd_merged(q3, k3, v3, do3, lse, delta, scale)
    # dk/dv: per-QUERY-head f32 partials from the kernel; the GQA group sum
    # is one cheap XLA reduce over [BKV, GROUP, S, D].
    dk = dk_p.reshape(bkv, group, seq, d).sum(axis=1).astype(k3.dtype)
    dv = dv_p.reshape(bkv, group, seq, d).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Head-packed path for head_dim 64 (two heads per 128-lane tile)
# ---------------------------------------------------------------------------
#
# At d=64 every [*, d] tile pads to 128 lanes in VMEM/registers, so the
# per-head kernels above run all vector work and memory movement half-empty;
# r4 profiling measured them at ~25% of peak while the same kernels at d=128
# reach parity with the dense matmuls (ROOFLINE.md).  The packed layout stores
# head pairs (2i, 2i+1) side by side in the lane dimension — q/k/v/o/dq tiles
# are [*, 128] with lanes 0:64 = even head, 64:128 = odd head — so all VPU ops
# and HBM<->VMEM traffic run full-width.  The MXU dots are reconstructed as
# full-width dots:
#   scores:  s_sum = q_pack @ k_packT   (= s_even + s_odd over 128 lanes)
#            s_dif = (q_pack * sign) @ k_packT  (= s_even - s_odd)
#            s_even/odd = (s_sum +/- s_dif) / 2
#   p @ v:   t_even = p_even @ v_pack -> [p_e v_e | p_e v_o]; select halves
#            against t_odd = p_odd @ v_pack.
# Each pair of half-width (K=64 or N=64) dots becomes one pair of full-width
# dots — the same MXU time as the padded originals (the 50% padding bound is
# information-theoretic for d=64) — but the lane-padding waste on everything
# else disappears, which is where the measured 2x sat.
#
# Two compute modes (DSTACK_TPU_FLASH_PACK_MODE, read at trace time; one
# global env governs ALL packed kernels):
#   sumdiff — the reconstruction above: every dot full-width, 2x the dot
#             FLOPs.  Measured-best on v5e in every kernel (default).
#   sliced  — lane-slice the packed tiles back to [*, 64] per head for each
#             dot and concat results; dot cost identical to unpacked, but
#             Mosaic lane slice/concat overhead outweighs the FLOP saving
#             on v5e (kept as a tuning knob for future chip generations).
#
# Numerics (sumdiff): the reconstruction loses ~ulp(|s_other_head|) per
# score; with same-magnitude heads this is below the bf16 input noise floor.
# Head pairing requires hq even and the pair to share a kv head (GQA group
# even) or pair up kv heads exactly (group == 1, MHA).


def _pack_mode(default: str) -> str:
    return _os.environ.get("DSTACK_TPU_FLASH_PACK_MODE", default)


def _packed_block_sizes(seq: int) -> tuple[int, int]:
    """Packed kernels carry TWO f32 score planes (one per head) plus the
    sum/diff intermediates, so they cannot run the unpacked path's square
    1024 blocks inside the 16 MB scoped-VMEM budget.  Asymmetric blocks
    (tall q block, moderate kv block) keep the loop efficiency of large
    blocks with [BQ, BK] planes that fit; (512, 512) is the v5e
    measured-best end-to-end (1024-wide q blocks OOM scoped VMEM)."""
    spec = _os.environ.get("DSTACK_TPU_FLASH_PACK_BLOCK", "512,512")
    if "," in spec:
        bq, bk = (int(x) for x in spec.split(","))
    else:
        bq = bk = int(spec)
    bq, bk = min(bq, seq), min(bk, seq)
    while seq % bq:
        bq //= 2
    while seq % bk:
        bk //= 2
    bk = min(bk, bq)  # the causal loop bounds assume bq % bk == 0
    return bq, bk


def _pack_heads(x):
    """[B, S, H, D] -> [B*H/2, S, 2D]: head pairs side by side in lanes."""
    b, s, h, d = x.shape
    x = x.transpose(0, 2, 1, 3)                      # [b, h, s, d]
    x = x.reshape(b, h // 2, 2, s, d).transpose(0, 1, 3, 2, 4)
    return x.reshape(b * (h // 2), s, 2 * d)


def _unpack_heads(xp, b):
    """Inverse of :func:`_pack_heads` -> [B, S, H, D]."""
    p, s, dd = xp.shape
    d = dd // 2
    h = 2 * p // b
    x = xp.reshape(b, h // 2, s, 2, d).transpose(0, 1, 3, 2, 4)
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _dup_lanes(x):
    """[B, S, Hkv, D] -> [B*Hkv, S, 2D] with the head in BOTH lane halves
    (GQA: one kv head serves both query heads of a pair)."""
    b, s, h, d = x.shape
    x3 = x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    return jnp.concatenate([x3, x3], axis=-1)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _scores_pair(q, q_signed, k, scale, mode, half):
    """Per-head score planes s0, s1 [BQ, BK] from packed q [BQ, 2D], k [BK, 2D]."""
    if mode == "sliced":
        s0 = _dot(q[:, :half], k[:, :half], ((1,), (1,))) * scale
        s1 = _dot(q[:, half:], k[:, half:], ((1,), (1,))) * scale
        return s0, s1
    s_sum = _dot(q, k, ((1,), (1,)))
    s_dif = _dot(q_signed, k, ((1,), (1,)))
    return (s_sum + s_dif) * (0.5 * scale), (s_sum - s_dif) * (0.5 * scale)


def _pv_pair(p0, p1, v, mode, half, lo):
    """Packed [BQ, 2D] accumulator contribution [p0 @ v_even | p1 @ v_odd]."""
    if mode == "sliced":
        t0 = _dot(p0.astype(v.dtype), v[:, :half], ((1,), (0,)))
        t1 = _dot(p1.astype(v.dtype), v[:, half:], ((1,), (0,)))
        return jnp.concatenate([t0, t1], axis=-1)
    t0 = _dot(p0.astype(v.dtype), v, ((1,), (0,)))
    t1 = _dot(p1.astype(v.dtype), v, ((1,), (0,)))
    return jnp.where(lo, t0, t1)


def _dp_pair(do, do_signed, v, mode, half):
    """dp0, dp1 [BQ, BK] = per-head do @ v^T from packed do, v [*, 2D]."""
    if mode == "sliced":
        dp0 = _dot(do[:, :half], v[:, :half], ((1,), (1,)))
        dp1 = _dot(do[:, half:], v[:, half:], ((1,), (1,)))
        return dp0, dp1
    dp_sum = _dot(do, v, ((1,), (1,)))
    dp_dif = _dot(do_signed, v, ((1,), (1,)))
    return (dp_sum + dp_dif) * 0.5, (dp_sum - dp_dif) * 0.5


def _rows_pair(a0, a1, b, mode, half, lo):
    """Packed [*, 2D] result [a0^T @ b_even | a1^T @ b_odd] (contract rows);
    used for the dv (p, do) and dk (ds, q) outer products."""
    if mode == "sliced":
        x0 = _dot(a0, b[:, :half], ((0,), (0,)))
        x1 = _dot(a1, b[:, half:], ((0,), (0,)))
        return jnp.concatenate([x0, x1], axis=-1)
    x0 = _dot(a0, b, ((0,), (0,)))
    x1 = _dot(a1, b, ((0,), (0,)))
    return jnp.where(lo, x0, x1)


def _fwd_packed_kernel(q_ref, k_ref, v_ref, o_ref, lse0_ref, lse1_ref,
                       *, scale, bq, bk, mode):
    iq = pl.program_id(1)
    q = q_ref[0]                                     # [BQ, 2D]
    half = q.shape[-1] // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * half), 1)
    lo = lane < half
    q_signed = q * jnp.where(lo, 1, -1).astype(q.dtype)

    def body(j, carry, *, masked):
        m0, l0, m1, l1, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :]
        v = v_ref[0, pl.ds(j * bk, bk), :]
        s0, s1 = _scores_pair(q, q_signed, k, scale, mode, half)
        if masked:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = qpos >= kpos
            s0 = jnp.where(keep, s0, _NEG_INF)
            s1 = jnp.where(keep, s1, _NEG_INF)
        m0n = jnp.maximum(m0, jnp.max(s0, axis=-1, keepdims=True))
        m1n = jnp.maximum(m1, jnp.max(s1, axis=-1, keepdims=True))
        p0 = jnp.exp(s0 - m0n)
        p1 = jnp.exp(s1 - m1n)
        a0 = jnp.exp(m0 - m0n)
        a1 = jnp.exp(m1 - m1n)
        l0 = l0 * a0 + jnp.sum(p0, axis=-1, keepdims=True)
        l1 = l1 * a1 + jnp.sum(p1, axis=-1, keepdims=True)
        t = _pv_pair(p0, p1, v, mode, half, lo)
        acc = acc * jnp.where(lo, a0, a1) + t
        return m0n, l0, m1n, l1, acc

    n_kv = (iq + 1) * bq // bk
    n_full = iq * bq // bk
    neg = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    z = jnp.zeros((bq, 1), jnp.float32)
    carry = (neg, z, neg, z, jnp.zeros((bq, 2 * half), jnp.float32))
    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(body, masked=False), carry)
    m0, l0, m1, l1, acc = jax.lax.fori_loop(
        n_full, n_kv, functools.partial(body, masked=True), carry)
    o_ref[0] = (acc / jnp.where(lo, l0, l1)).astype(o_ref.dtype)
    lse0_ref[0] = m0 + jnp.log(l0)
    lse1_ref[0] = m1 + jnp.log(l1)


def _fwd_packed(qp, kp, vp, scale):
    ph, seq, dd = qp.shape
    pkv = kp.shape[0]
    group = ph // pkv
    bq, bk = _packed_block_sizes(seq)
    kernel = functools.partial(_fwd_packed_kernel, scale=scale, bq=bq, bk=bk,
                               mode=_pack_mode("sumdiff"))
    return pl.pallas_call(
        kernel,
        grid=(ph, seq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dd), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, dd), lambda h, i: (h // group, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, dd), lambda h, i: (h // group, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dd), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda h, i: (h, i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ph, seq, dd), qp.dtype),
            jax.ShapeDtypeStruct((ph, seq, 1), jnp.float32),
            jax.ShapeDtypeStruct((ph, seq, 1), jnp.float32),
        ],
        name="flash_fwd_packed",
        interpret=_interpret(),
    )(qp, kp, vp)


def _bwd_merged_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse0_ref, lse1_ref,
                              d0_ref, d1_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                              *, scale, bq, bk, n_q, n_k, mode):
    """Single-pass backward: one program per (pair, kv block) computes this
    kv block's dk/dv AND accumulates every q block's dq contribution into a
    whole-sequence f32 VMEM scratch (flushed on the last kv block).

    vs the split dq/dkv kernels this shares the score and ds recomputation
    (10 instead of 14 full-width dots per block pair) and reads q/do from
    HBM once instead of twice.  Correct because the TPU grid is sequential:
    the scratch persists across jk steps of the same pair program row."""
    jk = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    half = k.shape[-1] // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * half), 1)
    lo = lane < half

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(i, carry, *, masked):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :]
        do = do_ref[0, pl.ds(i * bq, bq), :]
        lse0 = lse0_ref[0, pl.ds(i * bq, bq), :]
        lse1 = lse1_ref[0, pl.ds(i * bq, bq), :]
        delta0 = d0_ref[0, pl.ds(i * bq, bq), :]
        delta1 = d1_ref[0, pl.ds(i * bq, bq), :]
        sign = jnp.where(lo, 1, -1).astype(q.dtype)
        q_signed = q * sign
        do_signed = do * sign
        s0, s1 = _scores_pair(q, q_signed, k, scale, mode, half)
        if masked:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = qpos >= kpos
            s0 = jnp.where(keep, s0, _NEG_INF)
            s1 = jnp.where(keep, s1, _NEG_INF)
        p0 = jnp.exp(s0 - lse0)
        p1 = jnp.exp(s1 - lse1)
        dv = dv + _rows_pair(p0.astype(k.dtype), p1.astype(k.dtype), do,
                             mode, half, lo)
        dp0, dp1 = _dp_pair(do, do_signed, v, mode, half)
        ds0 = (p0 * (dp0 - delta0)).astype(k.dtype)
        ds1 = (p1 * (dp1 - delta1)).astype(k.dtype)
        dk = dk + _rows_pair(ds0, ds1, q, mode, half, lo)
        if mode == "sliced":
            u0 = _dot(ds0, k[:, :half], ((1,), (0,)))
            u1 = _dot(ds1, k[:, half:], ((1,), (0,)))
            u = jnp.concatenate([u0, u1], axis=-1)
        else:
            u0 = _dot(ds0, k, ((1,), (0,)))
            u1 = _dot(ds1, k, ((1,), (0,)))
            u = jnp.where(lo, u0, u1)
        dq_acc[pl.ds(i * bq, bq), :] += u
        return dk, dv

    dk = jnp.zeros((bk, 2 * half), jnp.float32)
    dv = jnp.zeros((bk, 2 * half), jnp.float32)
    i0 = jk * bk // bq
    i_diag_end = jnp.minimum(((jk + 1) * bk + bq - 1) // bq, n_q)
    dk, dv = jax.lax.fori_loop(
        i0, i_diag_end, functools.partial(body, masked=True), (dk, dv))
    dk, dv = jax.lax.fori_loop(
        i_diag_end, n_q, functools.partial(body, masked=False), (dk, dv))
    dk_ref[0] = dk * scale
    dv_ref[0] = dv

    @pl.when(jk == n_k - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_packed_merged(qp, kp, vp, dop, lse0, lse1, delta0, delta1, scale):
    ph, seq, dd = qp.shape
    pkv = kp.shape[0]
    group = ph // pkv
    bq, bk = _packed_block_sizes(seq)
    return pl.pallas_call(
        functools.partial(_bwd_merged_packed_kernel, scale=scale, bq=bq,
                          bk=bk, n_q=seq // bq, n_k=seq // bk,
                          mode=_pack_mode("sumdiff")),
        grid=(ph, seq // bk),
        in_specs=[
            pl.BlockSpec((1, seq, dd), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda h, j: (h // group, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda h, j: (h // group, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, dd), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, 1), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, seq, dd), lambda h, j: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda h, j: (h, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda h, j: (h, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ph, seq, dd), qp.dtype),
            jax.ShapeDtypeStruct((ph, seq, dd), jnp.float32),
            jax.ShapeDtypeStruct((ph, seq, dd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((seq, dd), jnp.float32)],
        name="flash_bwd_packed",
        interpret=_interpret(),
    )(qp, kp, vp, dop, lse0, lse1, delta0, delta1)


def _bwd_packed(res, dop):
    qp, kp, vp, op, lse0, lse1, scale = res
    ph, seq, dd = qp.shape
    pkv = kp.shape[0]
    group = ph // pkv
    half = dd // 2
    prod = (dop.astype(jnp.float32) * op.astype(jnp.float32))
    delta0 = prod[..., :half].sum(axis=-1, keepdims=True)
    delta1 = prod[..., half:].sum(axis=-1, keepdims=True)
    dqp, dk_p, dv_p = _bwd_packed_merged(
        qp, kp, vp, dop, lse0, lse1, delta0, delta1, scale)
    dkp = dk_p.reshape(pkv, group, seq, dd).sum(axis=1).astype(kp.dtype)
    dvp = dv_p.reshape(pkv, group, seq, dd).sum(axis=1).astype(vp.dtype)
    return dqp, dkp, dvp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash3_packed(qp, kp, vp, scale):
    o, _, _ = _fwd_packed(qp, kp, vp, scale)
    return o


def _flash3_packed_fwd(qp, kp, vp, scale):
    o, lse0, lse1 = _fwd_packed(qp, kp, vp, scale)
    return o, (qp, kp, vp, o, lse0, lse1)


def _flash3_packed_bwd(scale, res, do):
    return _bwd_packed(res + (scale,), do)


_flash3_packed.defvjp(_flash3_packed_fwd, _flash3_packed_bwd)


def _use_packed(d: int, hq: int, hkv: int) -> bool:
    if _os.environ.get("DSTACK_TPU_FLASH_PACK", "1") == "0":
        return False
    group = hq // hkv
    return d == 64 and hq % 2 == 0 and (group == 1 or group % 2 == 0)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash3(q3, k3, v3, scale):
    o, _ = _fwd(q3, k3, v3, scale)
    return o


def _flash3_fwd(q3, k3, v3, scale):
    o, lse = _fwd(q3, k3, v3, scale)
    return o, (q3, k3, v3, o, lse)


def _flash3_bwd(scale, res, do):
    dq, dk, dv = _bwd(res + (scale,), do)
    return dq, dk, dv


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention_sharded(mesh, q, k, v, *, batch_axes=("dcn", "data", "fsdp"),
                            head_axis="tensor"):
    """Mesh wrapper: batch sharded over ``batch_axes``, heads over
    ``head_axis``, sequence replicated (seq sharding goes through ring
    attention instead).  The kernel then runs purely locally per device.

    Nests inside partially-manual regions (the pipeline body): the wrapper
    resolves the ambient abstract mesh and manualizes only the axes its
    specs name, so an enclosing shard_map's manual axes (``stage``) pass
    through untouched.
    """
    from jax.sharding import PartitionSpec as P
    spec = P(batch_axes, None, head_axis, None)
    kwargs = {}
    cur = jax.sharding.get_abstract_mesh()
    if cur.axis_names:
        # nested inside a manual region: use the ambient mesh and only
        # manualize this wrapper's own axes (top-level calls keep the
        # default all-axes-manual form)
        mesh = cur
        kwargs["axis_names"] = {a for a in (*batch_axes, head_axis) if a}
    fn = jax.shard_map(
        flash_attention, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False, **kwargs,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Paged decode attention (serving): block tables, ragged lengths
# ---------------------------------------------------------------------------
#
# Single-query GQA attention for the serving engine's decode loop, reading
# K/V straight out of the paged pool (serving/paging.py) through
# scalar-prefetched block tables.  The XLA paged path first gathers each
# slot's blocks into a dense [B, span, Hkv, D] view — at a 4k span that
# gather IS the decode step's non-weight HBM bill, and it reads padding for
# every slot shorter than the span.  Here the grid walks (slot, table
# column) and the BlockSpec index_map turns the table entry into the page
# address, so only owned pages cross HBM, exactly once, with no
# intermediate view.  int8 KV pages ({"q","s"} per serving/quant.py)
# dequantize in-kernel after the page load — packed bytes are what stream.
#
# A block is one WHOLE page, all kv heads, and the pool is STORED in the
# block's form: [L, NUM_BLOCKS, BS, Hkv*D], lane = h*D + d.  The block's
# last two dims equal the array's — the only shape the TPU lowering
# accepts here (one head of a page would put 1 of Hkv in the
# second-to-last dim, which is neither full nor a multiple of 8; the
# described-v5e compile tests in tests/compute/test_tpu_compile.py hold
# this).  The kernel walks the heads with static lane slices.
#
# The operand IS the stored pool, layer and all: on the chip a custom
# call's operand is a buffer of its own in the default tiled layout, so a
# [.., Hkv, D] -> [.., Hkv*D] reshape or a per-layer slice of a stacked
# pool is a copy of the layer's whole pool, every layer of every step.
# The layer is a scalar-prefetched index the page's index map adds.
#
# Returns a NORMALIZED output plus the softmax logsumexp so the caller can
# merge other attention pieces (the engine's in-window KV buffer) without
# re-reading pages.  Slots with length 0 return o = 0, lse = -inf — exact
# zero weight under any logsumexp merge.


def _paged_decode_kernel(layer_ref, tables_ref, lengths_ref, q_ref, *rest,
                         scale, bs, nbk, quant):
    del layer_ref, tables_ref  # consumed by the index maps
    _, hkv, _, d = q_ref.shape
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    else:
        k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    length = lengths_ref[b]

    @pl.when(i * bs < length)
    def _compute():
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        for h in range(hkv):
            q = q_ref[0, h]                       # [G, D]
            k = k_ref[0, 0, :, h * d:(h + 1) * d]  # [BS, D]
            v = v_ref[0, 0, :, h * d:(h + 1) * d]
            if quant:
                k = (k.astype(jnp.float32)
                     * ks_ref[0, 0, :, h:h + 1]).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * vs_ref[0, 0, :, h:h + 1]).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale              # [G, BS]
            s = jnp.where(kpos < length, s, _NEG_INF)
            # at least one column is valid here (i*bs < length), so m_new
            # is finite and the m_prev = -inf first block gives alpha = 0
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc[h] = acc[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new

    @pl.when(i == nbk - 1)
    def _flush():
        l = l_scr[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            l > 0, m_scr[...] + jnp.log(safe_l), _NEG_INF)


def paged_decode_attention(q, k_pages, v_pages, layer, tables, lengths, *,
                           scale: float | None = None):
    """Paged single-token GQA decode attention over block tables.

    q: [B, Hkv, G, D] (query heads grouped under their kv head);
    k_pages/v_pages: the STACKED paged pools as the engine stores them,
    [L, NUM_BLOCKS, BS, Hkv*D] (lane = h*D + d), or int8 ``{"q", "s"}``
    dicts (scales [L, NUM_BLOCKS, BS, Hkv]); layer: int32 scalar (or [1]),
    the layer whose pages to read — addressed in place, never sliced out;
    tables: int32 [B, NBK] table columns (0 = NULL block) — pass a sliced
    table to bound the walk at a ragged bucket; lengths: int32 [B] valid
    KV rows per slot.

    Returns ``(o, lse)``: o float32 [B, Hkv, G, D] NORMALIZED over the
    slot's ``length`` cache rows, lse float32 [B, Hkv, G] (-inf where
    length == 0, with o = 0) for logsumexp-merging window/new-token
    attention on the caller side.  int4 pages are not supported — the
    engine keeps those on the XLA gather path.

    The kernel runs per device: under a mesh, call it inside ``shard_map``
    with the lane dim sharded (the engine does): a shard holds whole heads,
    (Hkv/tp)*D lanes.  Any lane width compiles, because the block spans
    the shard's whole last dim, but only a multiple of 128 is stored in
    the operand's form: the TPU compiler keeps any other pool with the
    blocks minor-most and converts all of it around the call.
    """
    quant = isinstance(k_pages, dict)
    if quant and "q4" in k_pages:
        raise NotImplementedError(
            "paged_decode_attention reads int8/bf16 pages; int4 caches "
            "use the XLA gather path")
    b, hkv, group, d = q.shape
    nbk = tables.shape[1]
    kq, vq = (k_pages["q"], v_pages["q"]) if quant else (k_pages, v_pages)
    bs = kq.shape[2]
    if kq.ndim != 4 or kq.shape[3] != hkv * d:
        raise ValueError(
            f"pages must be [L, NUM_BLOCKS, BS, Hkv*D = {hkv * d}], got "
            f"{kq.shape}")
    if scale is None:
        scale = d ** -0.5

    def whole(bb, i, layer, tables, lengths):
        return (bb, 0, 0, 0)

    def page(bb, i, layer, tables, lengths):
        # the table entry IS the page index, the layer its plane
        return (layer[0], tables[bb, i], 0, 0)

    def spec(block, index_map):
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)

    k_spec = spec((1, 1, bs, hkv * d), page)
    if quant:
        s_spec = spec((1, 1, bs, hkv), page)
        inputs = (q, kq, k_pages["s"], vq, v_pages["s"])
        in_specs = [k_spec, s_spec, k_spec, s_spec]
    else:
        inputs = (q, kq, vq)
        in_specs = [k_spec, k_spec]

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               nbk=nbk, quant=quant)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nbk),
            in_specs=[spec((1, hkv, group, d), whole)] + in_specs,
            out_specs=[spec((1, hkv, group, d), whole),
                       spec((1, hkv, group, 1), whole)],
            scratch_shapes=[
                pltpu.VMEM((hkv, group, d), jnp.float32),
                pltpu.VMEM((hkv, group, 1), jnp.float32),
                pltpu.VMEM((hkv, group, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
        ],
        name="paged_decode_attention",
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      lengths.astype(jnp.int32), *inputs)
    return o, lse[..., 0]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: float | None = None) -> jnp.ndarray:
    """Causal GQA attention, fused.  q: [B, S, Hq, D]; k, v: [B, S, Hkv, D].

    Differentiable (custom VJP recomputes scores blockwise).  Returns
    [B, S, Hq, D] in q's dtype.  Callers should check :func:`supports` first.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    if scale is None:
        scale = d ** -0.5
    if _use_packed(d, hq, hkv):
        qp = _pack_heads(q)
        if hq == hkv:                       # MHA: pair the kv heads too
            kp, vp = _pack_heads(k), _pack_heads(v)
        else:                               # GQA: one kv head serves the pair
            kp, vp = _dup_lanes(k), _dup_lanes(v)
        op = _flash3_packed(qp, kp, vp, scale)
        return _unpack_heads(op, b)
    q3 = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    o3 = _flash3(q3, k3, v3, scale)
    return o3.reshape(b, hq, s, d).transpose(0, 2, 1, 3)

