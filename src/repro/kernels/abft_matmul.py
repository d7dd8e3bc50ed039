"""Pallas TPU kernel: blocked matmul with *fused block-level ABFT*.

This is the TPU-native adaptation of the paper's thread-level ABFT
(DESIGN.md §2).  The GPU scheme fuses checksum generation into each CUDA
thread's sub-GEMM so that no extra HBM traffic is generated; the TPU
analogue is the Pallas grid block: each grid cell owns a (bm × bn) output
tile, marches down K in (bm × bk) · (bk × bn) steps with both tiles resident
in VMEM, and accumulates its ABFT checksums from those same VMEM tiles —
zero additional HBM loads/stores, exactly the paper's §3.5 design principle.

Compute-unit mapping (the key hardware adaptation): the main GEMM runs on
the MXU; the redundant checksum math is expressed as VPU-friendly
reductions / weighted row-sums so that, on a bandwidth-bound GEMM, the
redundant work occupies the *idle* vector unit instead of competing for MXU
issue slots.  (`jnp.sum` / elementwise ops lower to VPU; only the REPLICA
baseline re-issues MXU work, mirroring paper §4.)

Modes (static):
  '1s'      one-sided block ABFT (default; paper §5.2.2).  Per K step:
              b_sum  = Σ_j B_tile[:, j]                  (VPU, (bk,))
              chk   += A_tile @ b_sum                    (VPU weighted rowsum)
              bnd   += |A_tile| @ Σ_j |B_tile[:, j]|     (threshold bound)
            Final:  residual = |chk − Σ_j acc[:, j]|  → locates faulty row.
  '2s'      two-sided block ABFT: scalar residual per block (paper Fig. 7
            left), fewer VPU FLOPs, no row location.
  'replica' replicated-MMA-single-accumulation baseline (paper §4): the
            block matmul is re-issued on the MXU, accumulated into one
            (bm,) vector and compared against the row-sums of the original.

Fault injection: an optional FaultSpec corrupts the **main accumulator
only**, after the checksum path has consumed the same operands — modeling a
soft error in the MXU that the independent VPU checksum data path does not
see (paper §2.3 fault model).

VMEM budget per grid cell (bf16 operands, f32 accumulators):
    bm·bk·2 + bk·bn·2 + bm·bn·4 + 2·bm·128·4 bytes
with the default (bm, bk, bn) = (256, 512, 256): 0.25 + 0.25 + 0.25 +
0.25 MiB ≈ 1 MiB — comfortably inside a v5e core's VMEM even with double
buffering; all tile dims are multiples of the 128-lane MXU width.

Output layout (the TPU's (8, 128) tiling rule): the per-row checksum and
bound accumulators are (bm, 128) lane-replicated tiles, and the residual
and bound outputs are lane-dense ``(M, gn·128)`` arrays written one
(bm, 128) tile per grid cell ('2s': ``(gm·8, gn·128)``, one (8, 128) tile
holding the block scalar).  The wrapper (ops.py) reads lane 0 and
reshapes to the logical (gm, gn, bm) / (gm, gn) residuals.  The fault
vectors live in SMEM.  Kernels are validated in interpret mode against
ref.py and compiled for a described v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

MODES = ("1s", "2s", "replica")


LANES = 128          # lane width of a vreg: one output tile column


def _apply_fault(acc, fault_idx_ref, fault_val_ref, block_i, block_j):
    """Corrupt one element of the f32 accumulator tile per the fault spec.

    fault_idx (SMEM): (8,) int32 [block_i, block_j, row_in_block,
    col_in_block, enabled, bit, _, _];  fault_val (SMEM): (1,) f32 delta.
    """
    bm, bn = acc.shape
    here = (
        (fault_idx_ref[4] == 1)
        & (fault_idx_ref[0] == block_i)
        & (fault_idx_ref[1] == block_j)
    )
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    mask = (rows == fault_idx_ref[2]) & (cols == fault_idx_ref[3]) & here

    bit = fault_idx_ref[5]
    raw = jax.lax.bitcast_convert_type(acc, jnp.int32)
    flip_mask = jnp.left_shift(jnp.int32(1), jnp.maximum(bit, 0))
    flipped = jax.lax.bitcast_convert_type(raw ^ flip_mask, F32)
    corrupted = jnp.where(bit >= 0, flipped, acc + fault_val_ref[0])
    return jnp.where(mask, corrupted, acc)


def _kernel(
    x_ref, w_ref, fault_idx_ref, fault_val_ref,   # inputs
    y_ref, res_ref, bnd_ref,                      # outputs
    acc_ref, chk_ref, bnd_acc_ref,                # scratch
    *, gk: int, mode: str, out_dtype,
):
    # program_id must be read at kernel top level (not inside pl.when
    # bodies) for interpret-mode compatibility.
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        chk_ref[...] = jnp.zeros_like(chk_ref)
        bnd_acc_ref[...] = jnp.zeros_like(bnd_acc_ref)

    a = x_ref[...]
    b = w_ref[...]
    # Main GEMM contribution — MXU, f32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )

    af = a.astype(F32)
    bf = b.astype(F32)
    # checksum/bound accumulators are (rows, LANES) tiles whose lanes all
    # hold the same value: the keepdims reductions below broadcast into them
    if mode == "1s":
        b_sum = jnp.sum(bf, axis=1)                     # (bk,)  VPU
        b_abs = jnp.sum(jnp.abs(bf), axis=1)            # (bk,)  VPU
        # Weighted row-sum: Σ_k A[:, k] * b_sum[k] — VPU multiply-reduce,
        # NOT an MXU matvec (DESIGN.md §2).
        chk_ref[...] += jnp.sum(af * b_sum[None, :], axis=1, keepdims=True)
        bnd_acc_ref[...] += jnp.sum(jnp.abs(af) * b_abs[None, :], axis=1,
                                    keepdims=True)
    elif mode == "2s":
        a_sum = jnp.sum(af, axis=0, keepdims=True)      # (1, bk)
        a_abs = jnp.sum(jnp.abs(af), axis=0, keepdims=True)
        b_sum = jnp.sum(bf, axis=1)                     # (bk,)
        b_abs = jnp.sum(jnp.abs(bf), axis=1)
        chk_ref[...] += jnp.sum(a_sum * b_sum[None, :], axis=1,
                                keepdims=True)
        bnd_acc_ref[...] += jnp.sum(a_abs * b_abs[None, :], axis=1,
                                    keepdims=True)
    elif mode == "replica":
        # Redundant MXU pass, single-vector accumulation (paper §4).
        redo = jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=F32
        )
        chk_ref[...] += jnp.sum(redo, axis=1, keepdims=True)
        bnd_acc_ref[...] += jnp.sum(jnp.abs(redo), axis=1, keepdims=True)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    @pl.when(k == gk - 1)
    def _finalize():
        acc = _apply_fault(acc_ref[...], fault_idx_ref, fault_val_ref, i, j)
        y_ref[...] = acc.astype(out_dtype)
        rowsum = jnp.sum(acc, axis=1, keepdims=True)    # (bm, 1) VPU
        if mode == "2s":
            total = jnp.sum(rowsum, axis=0, keepdims=True)        # (1, 1)
            res = jnp.abs(chk_ref[...] - total)                   # (1, L)
            res_ref[...] = jnp.broadcast_to(res, res_ref.shape)
            bnd_ref[...] = jnp.broadcast_to(bnd_acc_ref[...], bnd_ref.shape)
        else:
            res_ref[...] = jnp.abs(chk_ref[...] - rowsum)
            bnd_ref[...] = bnd_acc_ref[...]


def abft_matmul_kernel(
    x: jnp.ndarray,
    w: jnp.ndarray,
    fault_idx: jnp.ndarray,
    fault_val: jnp.ndarray,
    *,
    bm: int,
    bk: int,
    bn: int,
    mode: str = "1s",
    out_dtype=jnp.bfloat16,
    interpret: bool = True,
):
    """Raw kernel entry; shapes must already be padded to block multiples.

    x: (M, K), w: (K, N) -> y (M, N) in out_dtype, plus lane-dense f32
    res/bnd tiles: (M, gn·128) for '1s'/'replica' (row r of block column
    j in lanes [128j, 128j + 128)), (gm·8, gn·128) for '2s' (one block
    scalar per (8, 128) tile).  ``ops.abft_matmul`` unpacks them.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        (m, k, n), (bm, bk, bn))
    gm, gk, gn = m // bm, k // bk, n // bn

    rows = 8 if mode == "2s" else bm
    res_shape = jax.ShapeDtypeStruct((gm * rows, gn * LANES), F32)
    res_spec = pl.BlockSpec((rows, LANES), lambda i, j, kk: (i, j))
    chk_rows = 1 if mode == "2s" else bm
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_kernel, gk=gk, mode=mode, out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            smem,
            smem,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            res_spec,
            res_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            res_shape,
            res_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), F32),              # main f32 accumulator
            pltpu.VMEM((chk_rows, LANES), F32),     # ABFT checksum
            pltpu.VMEM((chk_rows, LANES), F32),     # magnitude bound
        ],
        interpret=interpret,
    )(x, w, fault_idx, fault_val)
