"""Per-kernel validation: Pallas fused-ABFT matmul vs the pure-jnp oracle.

Sweeps shapes/dtypes/modes in interpret mode (CPU) per the brief; every
case asserts (i) the GEMM output matches the oracle, (ii) residuals match
the oracle's chunk-ordered computation, (iii) clean runs never flag.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.faults import FaultSpec
from repro.core.schemes import BlockShape
from repro.kernels import abft_matmul
from repro.kernels.ref import abft_matmul_ref, matmul_ref

jax.config.update("jax_enable_x64", False)

SHAPES = [
    # (m, k, n) — mixed thin/fat/ragged
    (8, 8, 8),
    (16, 128, 64),
    (96, 200, 130),     # non-multiples force padding
    (1, 512, 512),      # decode-like thin GEMM
    (256, 64, 8),
    (130, 514, 258),    # every dim ragged
]
DTYPES = [jnp.float32, jnp.bfloat16]
MODES = ["1s", "2s", "replica"]


def _tol(dtype):
    # accumulation order differs between the k-chunked kernel and the
    # oracle's single einsum — allow a few ulps of headroom
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_oracle(rng, shape, dtype, mode):
    m, k, n = shape
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)), dtype)
    y, chk = abft_matmul(x, w, mode=mode, out_dtype=jnp.float32)
    y_ref = matmul_ref(x, w, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), **_tol(dtype))
    assert not bool(chk.flag), (
        f"false positive: max res/tau="
        f"{float(jnp.max(chk.residual / chk.threshold))}")


@pytest.mark.parametrize("mode", ["1s", "2s"])
def test_kernel_residual_matches_ref_blocked(rng, mode):
    """Residual/bound outputs equal the oracle's block-structured values."""
    m, k, n = 128, 256, 128
    bm, bk, bn = 64, 64, 64
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    y, chk = abft_matmul(
        x, w, mode=mode, blocks=BlockShape(bm=bm, bk=bk, bn=bn),
        out_dtype=jnp.float32)
    y_ref, res_ref, bnd_ref = abft_matmul_ref(
        x, w, mode=mode, bm=bm, bk=bk, bn=bn, out_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=5e-4, atol=5e-4)
    # bounds are sums of |a||b| — deterministic up to fp association
    np.testing.assert_allclose(
        np.asarray(chk.residual), np.asarray(res_ref), atol=1e-2)


@pytest.mark.parametrize("mode", MODES)
def test_fault_detected_and_located(rng, mode):
    m, k, n = 128, 256, 128
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    row, col = 37, 101
    y, chk = abft_matmul(
        x, w, mode=mode, out_dtype=jnp.float32,
        fault=FaultSpec.value(row, col, 100.0))
    assert bool(chk.flag)
    # one-sided/replica residuals locate the faulty row within the block
    if mode != "2s":
        res = np.asarray(chk.residual)          # (gm, gn, bm)
        gm, gn, bm = res.shape
        hot = np.unravel_index(np.argmax(res), res.shape)
        assert hot[0] * bm + hot[2] == row


@pytest.mark.parametrize("bit", [31, 30, 28, 24])  # sign + exponent bits
def test_bitflip_detected(rng, bit):
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    y, chk = abft_matmul(
        x, w, mode="1s", out_dtype=jnp.float32,
        fault=FaultSpec.bitflip(10, 10, bit))
    # exponent-region flips change magnitude by >= 2x — always above tau
    assert bool(chk.flag)


def test_nan_corruption_flags(rng):
    """NaN in the accumulator must flag (NaN-safe compare)."""
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    y, chk = abft_matmul(
        x, w, mode="1s", out_dtype=jnp.float32,
        fault=FaultSpec.value(0, 0, float("nan")))
    assert bool(chk.flag)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(1, 96),
    k=st.integers(1, 160),
    n=st.integers(1, 96),
    scale=st.sampled_from([1e-3, 1.0, 1e3]),
    mode=st.sampled_from(MODES),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_no_false_positives(m, k, n, scale, mode, seed):
    """Invariant: a clean GEMM never flags, across shapes and scales."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)) * scale, jnp.float32)
    w = jnp.asarray(r.standard_normal((k, n)) * scale, jnp.float32)
    y, chk = abft_matmul(x, w, mode=mode, out_dtype=jnp.float32)
    assert not bool(chk.flag)
    y_ref = matmul_ref(x, w, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4 * scale * scale)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(2, 64),
    k=st.integers(8, 128),
    n=st.integers(2, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_large_fault_always_detected(m, k, n, seed):
    """Invariant: single faults well above the rounding bound are detected,
    at any output coordinate."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(r.standard_normal((k, n)), jnp.float32)
    row, col = int(r.integers(m)), int(r.integers(n))
    # magnitude >> tau ~ 16*eps*sqrt(k)*O(k*n): use 50x typical element
    delta = 50.0 * float(np.sqrt(k))
    y, chk = abft_matmul(
        x, w, mode="1s", out_dtype=jnp.float32,
        fault=FaultSpec.value(row, col, delta))
    assert bool(chk.flag)


def test_vmap_expert_batching(rng):
    """vmap over the kernel = per-expert protected GEMMs (MoE path)."""
    xe = jnp.asarray(rng.standard_normal((4, 16, 128)), jnp.float32)
    we = jnp.asarray(rng.standard_normal((4, 128, 64)), jnp.float32)
    yv, chkv = jax.vmap(
        lambda a, b: abft_matmul(a, b, mode="1s", out_dtype=jnp.float32)
    )(xe, we)
    y_ref = jnp.einsum("emk,ekn->emn", xe, we)
    np.testing.assert_allclose(np.asarray(yv), np.asarray(y_ref), rtol=1e-4)
    assert not bool(jnp.any(chkv.flag))


def test_block_clamping_thin_gemm(rng):
    """Thin GEMMs shrink blocks instead of padding to 256."""
    x = jnp.asarray(rng.standard_normal((2, 1024)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1024, 8)), jnp.float32)
    y, chk = abft_matmul(x, w, mode="1s", out_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(matmul_ref(x, w, jnp.float32)),
        rtol=5e-4, atol=5e-4)
    assert not bool(chk.flag)


@pytest.mark.parametrize("mode", MODES)
def test_kernel_gradients_match_plain_dot(rng, mode):
    """Reverse mode through the kernel (training on a block scheme): the
    gradients equal those of the plain dot, padding included."""
    x = jnp.asarray(rng.standard_normal((2, 12, 200)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((200, 130)), jnp.float32)

    def loss(mm):
        return lambda a, b: jnp.sum(jnp.tanh(mm(a, b)))

    kern = loss(lambda a, b: abft_matmul(a, b, mode=mode)[0])
    ref = loss(lambda a, b: jnp.matmul(a, b, precision="highest"))
    got = jax.grad(kern, argnums=(0, 1))(x, w)
    want = jax.grad(ref, argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=5e-4)
