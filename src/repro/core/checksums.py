"""ABFT checksum math shared by the global path and the Pallas kernels.

Floating-point note (DESIGN.md §6): the paper evaluates FP16 on GPUs; we
target bf16 with f32 accumulation on TPU.  Checksum equality therefore
becomes a *threshold* test.  Residuals are compared against a principled
bound built from the magnitude sum of the products entering the check:

    |check - recompute| <= tau,
    tau = atol + eps_acc * c(K) * Sigma|a_ik||b_kj|  (+ output-quantization
          term eps_out/2 * rowsum|y| when the checked output was downcast)

Any injected fault with |delta| > tau is detected; faults below tau are, by
construction, within the accumulated rounding noise of a correct GEMM at the
working precision.  NaN/Inf corruptions always trip the check (the compare
is written as ``~(residual <= tau)`` so NaN residuals flag).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Check einsums run at full f32 precision: on a TPU a default-precision f32
# dot rounds its operands to bf16 (one MXU pass), an error ~2^-9 of the
# bound that the f32 tolerance below does not allow for.
CHECK_PRECISION = jax.lax.Precision.HIGHEST

# Empirical safety factor over the sqrt-growth rounding model; calibrated by
# tests/test_checksums.py (hypothesis sweep: zero false positives at 8x the
# observed worst residual/bound ratio).
DEFAULT_C_FACTOR = 16.0
ATOL = 1e-30


def eps_of(dtype) -> float:
    return float(jnp.finfo(dtype).eps)


def tolerance_scale(k: int, acc_dtype=jnp.float32, c: float = DEFAULT_C_FACTOR):
    """eps_acc * c * sqrt(k): the relative threshold multiplier applied to
    the magnitude bound.  sqrt(k) reflects random-walk error growth of
    f32 summation over the contraction dimension."""
    return eps_of(acc_dtype) * c * math.sqrt(max(k, 1))


class CheckResult(NamedTuple):
    """Outcome of one ABFT check.  All fields are JAX arrays (pytree-safe).

    ``flag``: scalar bool — True iff a fault was detected (residual above
    threshold anywhere, or NaN/Inf in the residual).
    ``residual``: the raw |check - recompute| values (shape depends on the
    scheme: per-row for one-sided, scalar for two-sided/global-scalar).
    ``threshold``: matching thresholds.
    """

    flag: jnp.ndarray
    residual: jnp.ndarray
    threshold: jnp.ndarray

    @staticmethod
    def combine(*results: "CheckResult") -> "CheckResult":
        """Fold many checks into a single scalar flag (used when aggregating
        across layers inside a scanned stack)."""
        flags = [r.flag for r in results]
        out = flags[0]
        for f in flags[1:]:
            out = jnp.logical_or(out, f)
        return CheckResult(
            flag=out,
            residual=jnp.zeros((), F32),
            threshold=jnp.zeros((), F32),
        )

    @staticmethod
    def clean() -> "CheckResult":
        return CheckResult(
            flag=jnp.zeros((), bool),
            residual=jnp.zeros((), F32),
            threshold=jnp.zeros((), F32),
        )


def flag_from(residual, threshold):
    """NaN-safe threshold compare: NaN/Inf residuals always flag."""
    return jnp.logical_not(jnp.all(residual <= threshold))


# ----------------------------------------------------------------------
# Offline weight checksums (paper §2.5: built once, reused every request).
# ----------------------------------------------------------------------

def weight_row_checksum(w: jnp.ndarray) -> jnp.ndarray:
    """rowsum over the output dim: (k, n) -> (k,), f32."""
    return jnp.sum(w.astype(F32), axis=-1)


def weight_abs_checksum(w: jnp.ndarray) -> jnp.ndarray:
    """Magnitude companion used for the residual threshold."""
    return jnp.sum(jnp.abs(w.astype(F32)), axis=-1)


# ----------------------------------------------------------------------
# Global ABFT check (Hari et al.-style, adapted: left-applied so the
# offline weight checksum is the reused operand; residual locates the
# faulty output *row*).
# ----------------------------------------------------------------------

def global_row_check(
    x: jnp.ndarray,
    w_sum: jnp.ndarray,
    w_abs_sum: jnp.ndarray,
    y: jnp.ndarray,
    c_factor: float = DEFAULT_C_FACTOR,
) -> CheckResult:
    """Check y == x @ w using the offline checksum of w.

    x: (..., m, k); y: (..., m, n); w_sum/w_abs_sum: (k,).
    """
    k = x.shape[-1]
    xf = x.astype(F32)
    check = jnp.einsum("...mk,k->...m", xf, w_sum,
                       precision=CHECK_PRECISION)
    bound = jnp.einsum("...mk,k->...m", jnp.abs(xf), w_abs_sum,
                       precision=CHECK_PRECISION)
    yf = y.astype(F32)
    y_rowsum = jnp.sum(yf, axis=-1)
    residual = jnp.abs(check - y_rowsum)
    tau = ATOL + tolerance_scale(k) * bound
    if y.dtype != F32:
        # Output-quantization term: y was rounded to its storage dtype.
        tau = tau + 0.5 * eps_of(y.dtype) * jnp.sum(jnp.abs(yf), axis=-1)
    return CheckResult(flag=flag_from(residual, tau), residual=residual,
                       threshold=tau)


def global_scalar_check(
    x: jnp.ndarray,
    w_sum: jnp.ndarray,
    w_abs_sum: jnp.ndarray,
    y: jnp.ndarray,
    c_factor: float = DEFAULT_C_FACTOR,
) -> CheckResult:
    """Paper Fig. 1 single-dot-product variant: colsum(x) . w_sum vs sum(y).
    Cheapest possible global check; detects but does not locate."""
    k = x.shape[-1]
    xf = x.astype(F32)
    a_sum = jnp.sum(xf, axis=-2)
    a_abs = jnp.sum(jnp.abs(xf), axis=-2)
    check = jnp.einsum("...k,k->...", a_sum, w_sum,
                       precision=CHECK_PRECISION)
    bound = jnp.einsum("...k,k->...", a_abs, w_abs_sum,
                       precision=CHECK_PRECISION)
    yf = y.astype(F32)
    total = jnp.sum(yf, axis=(-1, -2))
    residual = jnp.abs(check - total)
    m = x.shape[-2]
    tau = ATOL + tolerance_scale(k * m) * bound
    if y.dtype != F32:
        tau = tau + 0.5 * eps_of(y.dtype) * jnp.sum(jnp.abs(yf), axis=(-1, -2))
    return CheckResult(flag=flag_from(residual, tau), residual=residual,
                       threshold=tau)
