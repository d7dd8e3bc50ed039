"""Fused-ABFT flash attention kernel: interpret-mode validation against the
softmax-attention oracle (kernels/ref.py) + fault detection through the online
softmax rescaling."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.faults import FaultSpec
from repro.kernels.flash_ops import flash_attention
from repro.kernels.ref import attention_ref

F32 = jnp.float32


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 64, 2, 16, 2),      # (B, L, H, D, KV) — GQA
    (2, 96, 4, 32, 4),      # MHA, ragged-ish length
])
def test_matches_naive_attention(rng, shape, causal):
    B, L, H, D, KV = shape
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), F32)
    k = jnp.asarray(rng.standard_normal((B, L, KV, D)), F32)
    v = jnp.asarray(rng.standard_normal((B, L, KV, D)), F32)
    o, chk = flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert not bool(chk.flag), (
        float(chk.residual[0]), float(chk.threshold[0]),
        float(chk.residual[1]), float(chk.threshold[1]))


def test_bf16_no_false_positive(rng):
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.bfloat16)
    o, chk = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    assert not bool(chk.flag)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2)


def test_fault_in_output_accumulator_detected(rng):
    """A corruption of the PV accumulator must trip the rescaled checksum
    (the invariant survives the online softmax)."""
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), F32)
    k = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), F32)
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), F32)
    o, chk = flash_attention(
        q, k, v, causal=True, bq=32, bk=32,
        fault=FaultSpec.value(row=10, col=3, delta=50.0))
    assert bool(chk.flag)


def test_clean_fault_disabled(rng):
    q = jnp.asarray(rng.standard_normal((1, 32, 1, 16)), F32)
    k = jnp.asarray(rng.standard_normal((1, 32, 1, 16)), F32)
    v = jnp.asarray(rng.standard_normal((1, 32, 1, 16)), F32)
    o, chk = flash_attention(q, k, v, fault=FaultSpec.none(), bq=16, bk=16)
    assert not bool(chk.flag)


def test_padded_lengths(rng):
    """Lq not a block multiple: causal padding path."""
    q = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), F32)
    k = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), F32)
    v = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), F32)
    o, chk = flash_attention(q, k, v, causal=True, bq=16, bk=16)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert o.shape == (1, 40, 2, 16)
    assert not bool(chk.flag)


# ---------------------------------------------------------------- decode

def test_flash_decode_matches_decode_attention_ragged(rng):
    """The decode entry accepts a per-row length vector: each batch row
    attends only its own valid cache prefix (the serving engine's
    vectorized cursor contract)."""
    from repro.kernels.flash_ops import flash_decode
    from repro.models.layers import decode_attention

    B, S, H, KV, D = 3, 40, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), F32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), F32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), F32)
    lengths = jnp.asarray([7, 40, 21], jnp.int32)
    out, chk = flash_decode(q, k, v, lengths, bk=16)
    ref = decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert not bool(chk.flag)

    # changing one row's length must change ONLY that row's output
    out2, _ = flash_decode(q, k, v, lengths.at[0].set(3), bk=16)
    assert not np.allclose(np.asarray(out2[0]), np.asarray(out[0]))
    np.testing.assert_array_equal(np.asarray(out2[1:]), np.asarray(out[1:]))


def test_flash_decode_scalar_length_broadcasts(rng):
    from repro.kernels.flash_ops import flash_decode
    from repro.models.layers import decode_attention

    q = jnp.asarray(rng.standard_normal((2, 1, 2, 16)), F32)
    k = jnp.asarray(rng.standard_normal((2, 24, 2, 16)), F32)
    v = jnp.asarray(rng.standard_normal((2, 24, 2, 16)), F32)
    out, chk = flash_decode(q, k, v, jnp.asarray(13, jnp.int32), bk=8)
    ref = decode_attention(q, k, v, jnp.asarray(13, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert not bool(chk.flag)
