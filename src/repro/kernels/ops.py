"""jit-ready wrappers around the fused ABFT matmul kernel.

Handles shape padding to block multiples, block-size clamping for thin
GEMMs, fault-spec translation to block coordinates, residual thresholding,
and interpret-mode selection (interpret=True everywhere except a real TPU
backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.checksums import ATOL, CheckResult, flag_from, tolerance_scale
from repro.core.faults import FaultSpec
from repro.core.hardware import on_tpu
from repro.core.schemes import BlockShape
from repro.kernels.abft_matmul import F32, LANES, abft_matmul_kernel


def default_interpret() -> bool:
    """Interpret mode everywhere except a TPU backend, where every Pallas
    kernel in this package runs compiled (the flash wrappers share this)."""
    return not on_tpu()


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _clamp_block(dim: int, block: int, align: int = 8) -> int:
    """Shrink a block to the (aligned) problem size for thin GEMMs so we do
    not burn VMEM on padding."""
    return min(block, _round_up(dim, align))


def _pad2d(a: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    pm, pn = m - a.shape[0], n - a.shape[1]
    if pm == 0 and pn == 0:
        return a
    return jnp.pad(a, ((0, pm), (0, pn)))


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "bm", "bk", "bn", "out_dtype", "interpret", "c_factor"),
)
def _abft_matmul_padded(
    x, w, fault_idx, fault_val, *, mode, bm, bk, bn, out_dtype, interpret,
    c_factor,
):
    y, res, bnd = abft_matmul_kernel(
        x, w, fault_idx, fault_val,
        bm=bm, bk=bk, bn=bn, mode=mode, out_dtype=out_dtype,
        interpret=interpret,
    )
    gm, gn = x.shape[0] // bm, w.shape[1] // bn
    # lane-dense kernel tiles -> logical residuals: lane 0 of each tile
    if mode == "2s":
        res, bnd = res[::8, ::LANES], bnd[::8, ::LANES]           # (gm, gn)
    else:                                                  # (gm, gn, bm)
        res, bnd = (a[:, ::LANES].reshape(gm, bm, gn).transpose(0, 2, 1)
                    for a in (res, bnd))
    k = x.shape[1]
    tau = ATOL + tolerance_scale(k, c=c_factor) * bnd
    flag = flag_from(res, tau)
    return y, res, tau, flag


# A pallas_call has no transpose rule, so reverse mode (training through a
# block scheme) gets an explicit VJP: the forward GEMM runs in the kernel,
# the backward GEMMs are plain f32-accumulated dots (dgrad/wgrad are not
# ABFT-protected).  ``static`` holds _abft_matmul_padded's static kwargs.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _abft_matmul_diff(x, w, fault_idx, fault_val, static):
    return _abft_matmul_padded(x, w, fault_idx, fault_val, **dict(static))


def _diff_fwd(x, w, fault_idx, fault_val, static):
    out = _abft_matmul_padded(x, w, fault_idx, fault_val, **dict(static))
    return out, (x, w)


def _diff_bwd(static, saved, cts):
    x, w = saved
    gy = cts[0]
    gx = jnp.matmul(gy, w.T, preferred_element_type=F32).astype(x.dtype)
    gw = jnp.matmul(x.T, gy, preferred_element_type=F32).astype(w.dtype)
    return gx, gw, None, None


_abft_matmul_diff.defvjp(_diff_fwd, _diff_bwd)


def abft_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    mode: str = "1s",
    blocks: BlockShape = BlockShape(),
    out_dtype=None,
    interpret: bool | None = None,
    fault: FaultSpec | None = None,
    c_factor: float = 16.0,
):
    """Fused-ABFT matmul: ``y = x @ w`` plus an in-kernel integrity check.

    x: (..., m, k) — leading dims are flattened into the GEMM M dim.
    w: (k, n).
    Returns (y, CheckResult).  ``CheckResult.residual`` is per (block, row)
    for one-sided mode — enough to locate the faulty output row.
    """
    if interpret is None:
        interpret = default_interpret()
    out_dtype = out_dtype or x.dtype

    *lead, m0, k0 = x.shape
    kw, n0 = w.shape
    assert k0 == kw, (x.shape, w.shape)
    x2 = x.reshape((-1, k0))
    m = x2.shape[0]

    bm = _clamp_block(m, blocks.bm)
    bk = _clamp_block(k0, blocks.bk)
    bn = _clamp_block(n0, blocks.bn)
    mp, kp, np_ = _round_up(m, bm), _round_up(k0, bk), _round_up(n0, bn)
    x2 = _pad2d(x2, mp, kp)
    wp = _pad2d(w, kp, np_)

    if fault is None:
        fault = FaultSpec.none()
    # Translate global output coordinates to (block, offset) pairs.
    fi = fault.row // bm
    fr = fault.row % bm
    fj = fault.col // bn
    fc = fault.col % bn
    fault_idx = jnp.stack(
        [fi, fj, fr, fc, fault.enabled, fault.bit,
         jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)]
    ).astype(jnp.int32)
    fault_val = fault.delta.reshape((1,)).astype(F32)

    y, res, tau, flag = _abft_matmul_diff(
        x2, wp, fault_idx, fault_val,
        (("mode", mode), ("bm", bm), ("bk", bk), ("bn", bn),
         ("out_dtype", jnp.dtype(out_dtype)), ("interpret", interpret),
         ("c_factor", c_factor)),
    )
    y = y[:m, :n0].reshape((*lead, m0, n0))
    return y, CheckResult(flag=flag, residual=res, threshold=tau)
