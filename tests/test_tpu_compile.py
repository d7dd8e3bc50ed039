"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler, given a described ``v5e:2x2``
topology, refuses what the chip would refuse (blocks that break the
(8, 128) tiling rule, too much VMEM).  Each case compiles a kernel through
the wrapper the model calls, with ``interpret=False``, at llama3.2-1b
widths, and checks the kernel is in the compiled HLO.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library), and the persistent
compilation cache is off around these compiles (they could be written
but never read back without a chip).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_ops, ops

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 2048, 8192, 32, 8, 64
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiles_to_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL),
                                 (D_MODEL, D_MODEL)])
@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("mode", ["1s", "2s", "replica"])
def test_abft_matmul_compiles(spec, mode, m, k, n):
    _compiles_to_kernel(
        lambda x, w: ops.abft_matmul(x, w, mode=mode, interpret=False),
        spec((m, k)), spec((k, n)))


def test_flash_attention_compiles(spec):
    L = 512
    _compiles_to_kernel(
        lambda q, k, v: flash_ops.flash_attention(q, k, v, interpret=False),
        spec((1, L, HEADS, HEAD_DIM)), spec((1, L, KV_HEADS, HEAD_DIM)),
        spec((1, L, KV_HEADS, HEAD_DIM)))


def test_flash_decode_compiles(spec):
    B, S = 8, 1024
    _compiles_to_kernel(
        lambda q, k, v, ln: flash_ops.flash_decode(q, k, v, ln,
                                                   interpret=False),
        spec((B, 1, HEADS, HEAD_DIM)), spec((B, S, KV_HEADS, HEAD_DIM)),
        spec((B, S, KV_HEADS, HEAD_DIM)), spec((B,), jnp.int32))


def test_flash_decode_paged_compiles(spec):
    B, S, block = 8, 1024, 16
    W = S // block
    _compiles_to_kernel(
        lambda q, k, v, t, ln: flash_ops.flash_decode_paged(
            q, k, v, t, ln, interpret=False),
        spec((B, 1, HEADS, HEAD_DIM)),
        spec((B * W, block, KV_HEADS, HEAD_DIM)),
        spec((B * W, block, KV_HEADS, HEAD_DIM)),
        spec((B, W), jnp.int32), spec((B,), jnp.int32))
