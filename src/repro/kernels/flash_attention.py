"""Pallas TPU kernel: flash attention with *fused in-VMEM ABFT*.

This is the "shared next lever" identified by the §Perf iterations: every
train/prefill cell is memory-bound on attention score-chunk HBM round
trips, and the paper's design principle (§3.5: add no memory traffic)
applies to attention's two GEMMs exactly as it does to linear layers:

  S = Q K^T   — protected by a one-sided checksum of the K tile:
                 chk_s = Q @ rowsum(K_tile)  vs  rowsum(S_tile),
                 checked per (q_block, k_block) while S is in VMEM;
  O = P V     — protected through the online-softmax rescaling: the
                 checksum accumulator rescales with the same correction
                 factor as the output accumulator, so
                 chk_pv = Σ corr·(P @ rowsum(V_tile))  vs  rowsum(acc)
                 holds at the end of the K loop.

The softmax itself is nonlinear (ABFT does not traverse exp); the paper's
treatment (replicate nonlinear ops) applies — here the exp/max/sum chain
is a small VPU computation whose inputs and outputs are *both* covered by
the two GEMM checks, bounding undetected-fault propagation to the
elementwise stage.

Kernel structure: grid (num_q_blocks, num_k_blocks), K innermost; online
softmax state (m, l), f32 accumulators, ABFT accumulators and magnitude
bounds in VMEM scratch.  Causal masking by absolute block positions.
Single-head 2-D problem; flash_ops.py wrappers vmap over (batch, heads).

Layout (the TPU's (8, 128) tiling rule, which must also hold for the
blocks vmap adds): every per-row statistic — softmax state, checksums,
bounds — is a (rows, 128) tile whose lanes hold the same value, and the
residual/bound outputs are (rows, 128) tiles of a lane-dense array; the
wrappers read lane 0.  Fault vectors and the decode kernels' block table
and length live in SMEM.  The PV product feeds the MXU ``p`` in the value
dtype, and the PV checksum consumes that same rounded ``p``, so the
invariant holds exactly at bf16.  Validated in interpret mode against
ref.py (tests/test_flash_attention.py) and compiled for a described v5e
(tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
LANES = 128          # lane width: per-row statistics are (rows, LANES)


def _init_scratch(m_ref, l_ref, acc_ref, *stat_refs):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for r in stat_refs:
        r[...] = jnp.zeros_like(r)


def _score_check(qf, kf, s, scale, ress_ref, bnds_ref):
    """ABFT check #1 on one score tile (VPU): chk_s = Q @ colsum(K_tile)
    vs rowsum(S_tile); running max of residual and bound per q row."""
    k_sum = jnp.sum(kf, axis=0)                    # (d,)
    k_abs = jnp.sum(jnp.abs(kf), axis=0)
    chk_s = jnp.sum(qf * k_sum[None, :], axis=1, keepdims=True) * scale
    bnd_s = jnp.sum(jnp.abs(qf) * k_abs[None, :], axis=1,
                    keepdims=True) * abs(scale)
    res_here = jnp.abs(chk_s - jnp.sum(s, axis=1, keepdims=True))
    ress_ref[...] = jnp.maximum(ress_ref[...], res_here)
    bnds_ref[...] = jnp.maximum(bnds_ref[...], bnd_s)


def _softmax_pv(s, v, m_ref, l_ref, acc_ref, chk_ref, bndc_ref):
    """Online-softmax update plus the PV GEMM and its ABFT accumulators,
    rescaled by the same correction so the invariant survives."""
    m_prev = m_ref[...]                            # (rows, LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)                 # (rows, LANES)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new

    pm = p.astype(v.dtype)                         # the MXU's operand
    pv = jax.lax.dot_general(
        pm, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
    acc_ref[...] = acc_ref[...] * corr[:, :1] + pv
    vf = v.astype(F32)
    v_sum = jnp.sum(vf, axis=1)                    # (bk,)
    v_abs = jnp.sum(jnp.abs(vf), axis=1)
    pf = pm.astype(F32)
    chk_ref[...] = chk_ref[...] * corr + jnp.sum(
        pf * v_sum[None, :], axis=1, keepdims=True)
    bndc_ref[...] = bndc_ref[...] * corr + jnp.sum(
        pf * v_abs[None, :], axis=1, keepdims=True)


def _finish(acc, l_ref, chk_ref, bndc_ref, ress_ref, bnds_ref,
            o_ref, res_s_ref, bnd_s_ref, res_pv_ref, bnd_pv_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc / l[:, :1]).astype(o_ref.dtype)
    res_pv_ref[...] = jnp.abs(chk_ref[...] - jnp.sum(acc, axis=1,
                                                     keepdims=True))
    bnd_pv_ref[...] = bndc_ref[...]
    res_s_ref[...] = ress_ref[...]
    bnd_s_ref[...] = bnds_ref[...]


def _kernel(
    q_ref, k_ref, v_ref, fault_ref,            # inputs
    o_ref, res_s_ref, bnd_s_ref, res_pv_ref, bnd_pv_ref,   # outputs
    m_ref, l_ref, acc_ref, chk_ref, bndc_ref, ress_ref, bnds_ref,  # scratch
    *, gk: int, bq: int, bk: int, causal: bool, scale: float,
):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref, chk_ref, bndc_ref, ress_ref,
                      bnds_ref)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]

    # ---- QK^T on the MXU, f32 accumulation, and its checksum (VPU)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * scale
    _score_check(q.astype(F32), k.astype(F32), s, scale, ress_ref, bnds_ref)

    # ---- causal mask by absolute positions
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    _softmax_pv(s, v, m_ref, l_ref, acc_ref, chk_ref, bndc_ref)

    @pl.when(ki == gk - 1)
    def _finalize():
        acc = acc_ref[...]
        # optional fault: corrupt the output accumulator only (the ABFT
        # data path consumed the same tiles independently)
        here = (fault_ref[4] == 1) & (fault_ref[0] == qi)
        rows = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        mask = (rows == fault_ref[2]) & (cols == fault_ref[3]) & here
        delta = jax.lax.bitcast_convert_type(
            jnp.full(acc.shape, fault_ref[5], jnp.int32), F32)
        acc = jnp.where(mask, acc + delta, acc)
        _finish(acc, l_ref, chk_ref, bndc_ref, ress_ref, bnds_ref,
                o_ref, res_s_ref, bnd_s_ref, res_pv_ref, bnd_pv_ref)


def _paged_decode_kernel(
    table_ref, len_ref,                            # scalar prefetch
    q_ref, k_ref, v_ref,                           # inputs
    o_ref, res_s_ref, bnd_s_ref, res_pv_ref, bnd_pv_ref,   # outputs
    m_ref, l_ref, acc_ref, chk_ref, bndc_ref, ress_ref, bnds_ref,  # scratch
    *, gk: int, bs: int, scale: float,
):
    """Paged decode tile: the block table is a scalar-prefetch operand,
    so grid step ``j`` DMAs physical block ``table[j]`` of the KV pool
    straight into VMEM — no gathered (B, W*block_size) copy of the cache
    is ever materialized (the XLA reference path's ``paged_gather``).
    The q tile carries all ``gq`` query heads of ONE kv head (GQA
    grouping), so the pool is shared rather than head-replicated.

    Masking runs in LOGICAL coordinates (``j * block_size + offset``)
    against ``len_ref``, and — unlike the dense cache, which is zero
    beyond the row's length — invalid positions here may hold ALIEN data
    (sentinel tails clamped by the wrapper point at other requests'
    blocks; reused blocks keep stale KV).  Both ABFT score-check sides
    (checksum, residual, bound) are therefore restricted to the valid
    columns: the invalid columns' scores are discarded before softmax
    anyway, and letting alien magnitudes into the bound would inflate
    the detection threshold and mask real faults.  The PV check needs no
    extra masking (p == 0 at invalid columns)."""
    ki = pl.program_id(0)

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref, chk_ref, bndc_ref, ress_ref,
                      bnds_ref)

    q = q_ref[...]                                 # (gq, d)
    k = k_ref[0]                                   # (bs, d)  one pool block
    v = v_ref[0]                                   # (bs, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * scale

    # validity in logical token coordinates (see docstring), as a row
    # mask over the scores and a column mask over the key tile
    k_row = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    k_col = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    valid = k_row < len_ref[0]                     # (1, bs)
    kf = jnp.where(k_col < len_ref[0], k.astype(F32), 0.0)

    # ABFT check #1, restricted to the valid key columns
    _score_check(q.astype(F32), kf, jnp.where(valid, s, 0.0), scale,
                 ress_ref, bnds_ref)

    s = jnp.where(valid, s, NEG_INF)
    _softmax_pv(s, v, m_ref, l_ref, acc_ref, chk_ref, bndc_ref)

    @pl.when(ki == gk - 1)
    def _finalize():
        _finish(acc_ref[...], l_ref, chk_ref, bndc_ref, ress_ref, bnds_ref,
                o_ref, res_s_ref, bnd_s_ref, res_pv_ref, bnd_pv_ref)


def _stat_scratch(rows: int, dv: int) -> list:
    """m, l, acc, pv checksum, pv bound, scores residual (max over k),
    scores bound — per-row statistics as lane-replicated tiles."""
    return [pltpu.VMEM((rows, LANES), F32), pltpu.VMEM((rows, LANES), F32),
            pltpu.VMEM((rows, dv), F32)] + [
        pltpu.VMEM((rows, LANES), F32) for _ in range(4)]


def flash_decode_paged_kernel(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    table: jnp.ndarray,
    length: jnp.ndarray,
    *,
    scale: float | None = None,
    out_dtype=None,
    interpret: bool = True,
):
    """Fused-ABFT paged decode attention for one kv head.

    q: (gq, d) — the ``gq`` query heads sharing this kv head (GQA
    grouping keeps the pool un-replicated); k_pool: (NB, BS, d);
    v_pool: (NB, BS, dv) — the physical block pools; table: (W,) int32
    physical block ids for this row (tail entries must be clamped to a
    valid id — they are masked by ``length``); length: (1,) int32 valid
    logical cache length.
    Returns (o (gq, dv), res_s, bnd_s, res_pv, bnd_pv), checks as
    lane-replicated (gq, 128) tiles.
    """
    gq, d = q.shape
    NB, BS, dv = v_pool.shape
    W = table.shape[0]
    scale = scale if scale is not None else d ** -0.5
    out_dtype = out_dtype or q.dtype

    kernel = functools.partial(_paged_decode_kernel, gk=W, bs=BS,
                               scale=scale)
    vec_spec = pl.BlockSpec((gq, LANES), lambda j, t, ln: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(W,),
        in_specs=[
            pl.BlockSpec((gq, d), lambda j, t, ln: (0, 0)),
            pl.BlockSpec((1, BS, d), lambda j, t, ln: (t[j], 0, 0)),
            pl.BlockSpec((1, BS, dv), lambda j, t, ln: (t[j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gq, dv), lambda j, t, ln: (0, 0)),
            vec_spec, vec_spec, vec_spec, vec_spec,
        ],
        scratch_shapes=_stat_scratch(gq, dv),
    )
    vec = jax.ShapeDtypeStruct((gq, LANES), F32)
    o, rs, bs_, rp, bp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((gq, dv), out_dtype),
                   vec, vec, vec, vec],
        interpret=interpret,
    )(table, length, q, k_pool, v_pool)
    return o, rs, bs_, rp, bp


def flash_decode_kernel(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length: jnp.ndarray,
    *,
    bk: int,
    scale: float | None = None,
    out_dtype=None,
    interpret: bool = True,
):
    """Fused-ABFT decode attention of the ``gq`` query heads of one kv
    head against a CONTIGUOUS cache row — the degenerate paged problem
    with the identity block table, so one kernel body serves both
    layouts (a dense row is a pool whose s-th block is block s).

    q: (gq, d); k: (S, d); v: (S, dv) — S padded to a bk multiple;
    length: (1,) int32 valid cache length for this row.
    Returns (o (gq, dv), res_s, bnd_s, res_pv, bnd_pv), checks as
    lane-replicated (gq, 128) tiles.
    """
    _, d = q.shape
    S, dv = v.shape
    assert S % bk == 0, (S, bk)
    gk = S // bk
    return flash_decode_paged_kernel(
        q, k.reshape(gk, bk, d), v.reshape(gk, bk, dv),
        jnp.arange(gk, dtype=jnp.int32), length,
        scale=scale, out_dtype=out_dtype, interpret=interpret)


def flash_attention_kernel(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    fault: jnp.ndarray,
    *,
    bq: int,
    bk: int,
    causal: bool = True,
    scale: float | None = None,
    out_dtype=None,
    interpret: bool = True,
):
    """Single-head fused-ABFT flash attention.

    q: (Lq, d), k: (Lk, d), v: (Lk, dv) — padded to block multiples.
    fault: (6,) int32 [q_block, _, row, col, enabled, delta_bits].
    Returns (o (Lq, dv), res_s, bnd_s, res_pv, bnd_pv) with per-q-row
    residual/bound values as lane-replicated (Lq, 128) arrays.
    """
    Lq, d = q.shape
    Lk, dv = v.shape
    assert Lq % bq == 0 and Lk % bk == 0, ((Lq, Lk), (bq, bk))
    gq, gk = Lq // bq, Lk // bk
    scale = scale if scale is not None else d ** -0.5
    out_dtype = out_dtype or q.dtype

    kernel = functools.partial(
        _kernel, gk=gk, bq=bq, bk=bk, causal=causal, scale=scale)
    vec_spec = pl.BlockSpec((bq, LANES), lambda i, j: (i, 0))
    vec = jax.ShapeDtypeStruct((Lq, LANES), F32)
    o, rs, bs, rp, bp = pl.pallas_call(
        kernel,
        grid=(gq, gk),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, dv), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bq, dv), lambda i, j: (i, 0)),
            vec_spec, vec_spec, vec_spec, vec_spec,
        ],
        out_shape=[jax.ShapeDtypeStruct((Lq, dv), out_dtype),
                   vec, vec, vec, vec],
        scratch_shapes=_stat_scratch(bq, dv),
        interpret=interpret,
    )(q, k, v, fault)
    return o, rs, bs, rp, bp
