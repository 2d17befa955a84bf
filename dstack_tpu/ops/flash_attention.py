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
# every slot shorter than the span.  Here only owned pages cross HBM,
# exactly once, with no intermediate view.  int8 KV pages ({"q","s"} per
# serving/quant.py) stream as packed bytes; their scales are applied
# in-kernel.
#
# What a grid step is.  The grid is (slot, ceil(table columns / P)): a
# step covers a COMPUTE BLOCK of P pages, P * BS rows of every kv head.  A
# grid step costs a fraction of a microsecond whatever it reads, and a
# one-page step reads for less than that, so a walk of one page a step is
# bound by the number of steps, not by bytes: P pages a step divide that
# overhead by P and give the matrix unit P * BS rows at a time.
#
# Who fetches.  The pools stay in HBM as they are stored, [L, NUM_BLOCKS,
# BS, Hkv*D] with lane = h*D + d (operands in ``pl.ANY``: on the chip a
# custom call's operand is a buffer of its own, so a [.., Hkv, D] ->
# [.., Hkv*D] reshape or a per-layer slice of a stacked pool would copy the
# layer's whole pool every layer of every step; the layer is a
# scalar-prefetched index).  The kernel copies a block's pages itself, one
# ``make_async_copy`` a page (``pool.at[layer, tables[slot, column]]``: a
# contiguous BS x Hkv*D tile), into a double-buffered VMEM scratch.  The
# copies of the NEXT live step (the slot's next block, or the first block
# of the next slot that has any rows) start before this step computes, so
# only the call's first fetch is exposed.  A step past a slot's length
# starts no copy and computes nothing; a block the length cuts copies only
# its live pages.
#
# What P is derived from.  The bytes of a page against a fixed budget of
# VMEM for the scratch (K and V, two buffers each): the largest power of
# two that fits, at most the table's width (:func:`_pages_per_step`).  int8
# pages have half the bytes and take twice the pages.  The table's width
# need not divide: the last block is cut like any other.
#
# The dead-row hazard.  Rows of the scratch that no copy filled (dead pages
# of a cut block) hold whatever was there, and rows of a live page past the
# slot's length hold whatever the pool holds.  Masking the scores alone is
# not enough: a probability of 0 times a NaN in V is NaN.  V's rows past the
# length are zeroed in the scratch before the product (K's need nothing: a
# select on the score drops a NaN).  An int8 page holds no NaN; there it is
# the scales of those rows that are zeroed.
#
# int8 pages are never dequantized.  A row of the block-diagonal query is
# nonzero on one head's lanes only, so that head's scale of a cache row
# can multiply the score (K) and the probability (V) instead of the page:
# [rows, Hkv] scales become [Hq, rows] by a 0/1 product, and the pages go
# to the matrix unit as they are (int8 -> bf16 is exact).
#
# All heads at once.  The scores of a block are ONE product, a
# block-diagonal query [Hq, Hkv*D] (row h*G+g holds q[h, g] on head h's
# lanes, zero elsewhere; built in-kernel once a slot) against the block's
# K [P*BS, Hkv*D], and P.V one product into an [Hq, Hkv*D] accumulator
# whose diagonal blocks are the output.  No head is sliced out of the lanes
# in the walk, whatever D and G are: each K/V element is loaded into the
# matrix unit once either way, and the off-diagonal products ride along on
# rows that would otherwise be idle (G rows of 128).
#
# Returns a NORMALIZED output plus the softmax logsumexp so the caller can
# merge other attention pieces (the engine's in-window KV buffer) without
# re-reading pages.  Slots with length 0 return o = 0, lse = -inf — exact
# zero weight under any logsumexp merge.

#: VMEM the K and V block buffers may take together (two buffers each): a
#: quarter of v5e's 16 MiB scoped default, which leaves the rest to the
#: accumulators, the scores and the compiler's own temporaries
_PAGED_SCRATCH_BYTES = 4 * 1024 * 1024


def _pages_per_step(page_bytes: int, table_width: int) -> int:
    """Pages a grid step fetches and attends: the largest power of two whose
    K and V double buffers (4 x P x ``page_bytes``) fit the scratch budget,
    at most the largest power of two <= the table's width."""
    p = 1
    while (8 * p * page_bytes <= _PAGED_SCRATCH_BYTES
           and 2 * p <= table_width):
        p *= 2
    return p


def _paged_decode_kernel(layer_ref, tables_ref, lengths_ref, q_ref, *rest,
                         scale, bs, pages, nbk, hkv, quant):
    """One grid step (slot ``b``, compute block ``i``) of the paged walk;
    see the comment block above for the form."""
    if quant:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, lse_ref, k_buf, v_buf, ks_buf,
         vs_buf, sems, state, qbd, acc, m_scr, l_scr) = rest
        sources = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                   (vs_hbm, vs_buf))
    else:
        (k_hbm, v_hbm, o_ref, lse_ref, k_buf, v_buf, sems, state, qbd, acc,
         m_scr, l_scr) = rest
        sources = ((k_hbm, k_buf), (v_hbm, v_buf))
    _, hq, d = q_ref.shape
    group = hq // hkv
    width = qbd.shape[1]        # Hkv*D, padded to whole lane tiles
    rows = pages * bs
    nslots = pl.num_programs(0)
    b = pl.program_id(0)
    i = pl.program_id(1)
    layer = layer_ref[0]

    def rows_of(slot):
        # no walk passes the table: a length beyond it attends what the
        # table has, as a grid that ends with the table always did
        return jnp.minimum(lengths_ref[slot], nbk * bs)

    def block_copies(slot, blk, buf, act):
        """Start (or wait for) the copies of the LIVE pages of block ``blk``
        of ``slot`` into buffer ``buf``: one loop a call site, its trip
        count the pages the slot's length reaches in this block."""
        live = jnp.clip(pl.cdiv(rows_of(slot) - blk * rows, bs), 0, pages)

        def page(j, carry):
            at = tables_ref[slot, blk * pages + j]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for n, (hbm, vmem) in enumerate(sources):
                act(pltpu.make_async_copy(
                    hbm.at[layer, at], vmem.at[buf, dst], sems.at[buf, n]))
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    def start(slot, blk, buf):
        block_copies(slot, blk, buf, lambda copy: copy.start())

    def wait(slot, blk, buf):
        block_copies(slot, blk, buf, lambda copy: copy.wait())

    length = rows_of(b)

    @pl.when((b == 0) & (i == 0))
    def _first_step():
        state[0] = 0   # the buffer the next live step reads
        state[1] = 0   # 1 once a live step has run: each fetches for the next

    @pl.when(i == 0)
    def _init_slot():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        # the block-diagonal query: every head's q tiled along the lanes by
        # a 0/1 product (exact), then everything off head h's lanes dropped
        tile = (jax.lax.broadcasted_iota(jnp.int32, (d, width), 1) % d
                == jax.lax.broadcasted_iota(jnp.int32, (d, width), 0))
        tiled = jax.lax.dot_general(
            q_ref[0], tile.astype(q_ref.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Hq, width]
        own = (jax.lax.broadcasted_iota(jnp.int32, (hq, width), 0) // group
               == jax.lax.broadcasted_iota(jnp.int32, (hq, width), 1) // d)
        qbd[...] = jnp.where(own, tiled, 0.0).astype(qbd.dtype)

    @pl.when(i * rows < length)
    def _live_step():
        buf = state[0]

        @pl.when(state[1] == 0)
        def _():    # the call's first live step: nobody fetched for it
            start(b, i, buf)

        # the next live step in grid order: this slot's next block, else
        # block 0 of the next slot that has rows
        def next_slot():
            return jax.lax.while_loop(
                lambda s: (s < nslots)
                & (lengths_ref[jnp.minimum(s, nslots - 1)] <= 0),
                lambda s: s + 1, b + 1)

        more = (i + 1) * rows < length
        nb = jax.lax.cond(more, lambda: b, next_slot)

        @pl.when(nb < nslots)
        def _():
            start(nb, jnp.where(more, i + 1, 0), 1 - buf)

        state[0] = 1 - buf
        state[1] = 1
        wait(b, i, buf)

        base = i * rows
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        q = qbd[...]                                     # [Hq, width]
        if quant:
            # the scales never meet the pages: a row of q is nonzero on
            # one head's lanes only, so that head's scale of a cache row
            # multiplies the score (K) or the probability (V) instead;
            # int8 -> q's dtype is exact.  [rows, Hkv] scales -> [Hq, rows]
            # by a 0/1 product (exact at HIGHEST)
            heads = ks_buf.shape[2]
            pick = (jax.lax.broadcasted_iota(jnp.int32, (hq, heads), 0)
                    // group
                    == jax.lax.broadcasted_iota(jnp.int32, (hq, heads), 1)
                    ).astype(jnp.float32)

            def per_head(scales):
                return jax.lax.dot_general(
                    pick, scales[buf], (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)  # [Hq, rows]

            # V's rows past the length hold finite int8 whatever is there;
            # it is their scales a NaN could come in by
            k_scale = per_head(ks_buf)
            v_scale = jnp.where(kpos < length, per_head(vs_buf), 0.0)
            k = k_buf[buf].astype(q.dtype)
            v = v_buf[buf].astype(q.dtype)
        else:
            @pl.when(base + rows > length)
            def _zero_dead_rows():
                row = base + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                v_buf[buf] = jnp.where(row < length, v_buf[buf],
                                       jnp.zeros_like(v_buf[buf]))

            k, v = k_buf[buf], v_buf[buf]                # [rows, width]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hq, rows]
        if quant:
            s = s * k_scale
        s = jnp.where(kpos < length, s, _NEG_INF)
        # at least one column is valid here (base < length), so m_new is
        # finite and the m_prev = -inf first block gives alpha = 0
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = p * v_scale
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Hq, width]
        m_scr[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        l = l_scr[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        lse_ref[0] = jnp.where(l > 0, m_scr[...] + jnp.log(safe_l), _NEG_INF)
        for h in range(hkv):     # the accumulator's diagonal blocks
            r = slice(h * group, (h + 1) * group)
            o_ref[0, r, :] = (acc[r, h * d:(h + 1) * d]
                              / safe_l[r]).astype(o_ref.dtype)


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
    KV rows per slot, at most NBK * BS.

    A grid step attends a block of P pages that the kernel fetches itself
    out of the pools in HBM, double-buffered, the next live block's copies
    in flight while this one computes; P follows from the bytes of a page
    (:func:`_pages_per_step`), not from an option.  Pages past a slot's
    length are neither fetched nor read: what the NULL block, unowned pages
    or the rows of a page past the length hold cannot reach the output.

    Returns ``(o, lse)``: o float32 [B, Hkv, G, D] NORMALIZED over the
    slot's ``length`` cache rows, lse float32 [B, Hkv, G] (-inf where
    length == 0, with o = 0) for logsumexp-merging window/new-token
    attention on the caller side.  int4 pages are not supported — the
    engine keeps those on the XLA gather path.

    The kernel runs per device: under a mesh, call it inside ``shard_map``
    with the lane dim sharded (the engine does): a shard holds whole heads,
    (Hkv/tp)*D lanes.  Any lane width compiles, because a page's copy spans
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
    hq, width = hkv * group, hkv * d
    nbk = tables.shape[1]
    kq, vq = (k_pages["q"], v_pages["q"]) if quant else (k_pages, v_pages)
    bs = kq.shape[2]
    if kq.ndim != 4 or kq.shape[3] != width:
        raise ValueError(
            f"pages must be [L, NUM_BLOCKS, BS, Hkv*D = {width}], got "
            f"{kq.shape}")
    if scale is None:
        scale = d ** -0.5

    def lane_tiles(a):
        # a page's copy has to span whole 128-lane tiles of its source.  A
        # pool of another width is not stored in the operand's form anyway
        # (the compiler converts all of it around the call, see above):
        # the conversion is this pad
        short = -a.shape[-1] % 128
        return jnp.pad(a, ((0, 0),) * 3 + ((0, short),)) if short else a

    def slot_block(bb, i, layer, tables, lengths):
        return (bb, 0, 0)

    def spec(block):
        return pl.BlockSpec(block, slot_block, memory_space=pltpu.VMEM)

    kq, vq = lane_tiles(kq), lane_tiles(vq)
    width = kq.shape[3]
    pages = _pages_per_step(bs * width * kq.dtype.itemsize, nbk)
    rows = pages * bs
    buffers = [pltpu.VMEM((2, rows, width), kq.dtype)] * 2
    if quant:
        ks, vs = lane_tiles(k_pages["s"]), lane_tiles(v_pages["s"])
        inputs = (kq, ks, vq, vs)
        buffers += [pltpu.VMEM((2, rows, ks.shape[3]), jnp.float32)] * 2
    else:
        inputs = (kq, vq)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               pages=pages, nbk=nbk, hkv=hkv, quant=quant)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, pl.cdiv(nbk, pages)),
            in_specs=[spec((1, hq, d))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(inputs),
            out_specs=[spec((1, hq, d)), spec((1, hq, 1))],
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((2, len(inputs))),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((hq, width), q.dtype),       # block-diagonal q
                pltpu.VMEM((hq, width), jnp.float32),   # accumulator
                pltpu.VMEM((hq, 1), jnp.float32),       # running max
                pltpu.VMEM((hq, 1), jnp.float32),       # running sum
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_decode_attention",
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      lengths.astype(jnp.int32), q.reshape(b, hq, d), *inputs)
    return o.reshape(b, hkv, group, d), lse.reshape(b, hkv, group)


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

