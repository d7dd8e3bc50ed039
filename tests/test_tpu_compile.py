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
import re

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


@pytest.mark.parametrize("entry", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("chips", [1, 4])
def test_layer_loop_keeps_cache_storage_layout(topo, entry, chips):
    """The stacked cache rides the layer loop in the layout the chip
    stores it in.  With 64-wide heads a v5e keeps positions minor
    (unpadded); a scatter into the carried cache, or a reader's
    preference, would otherwise pull the whole loop state into another
    layout (padded 2x here) and relayout the cache into and out of the
    loop on every call.

    One chip pins the carry to that layout (``LayerCtx.cache_device``):
    the program holds the cache in one layout and copies each leaf whole
    once, the entry copy of the input, which is not donated.  A 4-way
    ``model`` mesh, as ``MeshExecutor`` serves, is left unpinned (the
    partitioner would gather the whole cache onto every device to apply
    the pin): its decode keeps the storage layout, and its chunk program
    relayouts each device's shard into the loop and back, two copies a
    leaf.  Neither ever holds more than a shard of the cache."""
    import numpy as np
    from jax.experimental.layout import Layout
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from repro.configs import get_config, scaled_down
    from repro.core import ABFTConfig, Scheme
    from repro.distributed.mesh import make_hints
    from repro.distributed.sharding import (
        cache_specs,
        make_sharding,
        param_specs,
    )
    from repro.models import ModelFault, build_model
    from repro.models.layers import LayerCtx
    from repro.serve.runner import ModelRunner

    dev = topo.devices[0]
    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=4, d_model=256,
                      n_heads=4, n_kv_heads=4, head_dim=HEAD_DIM,
                      d_ff=512, vocab_size=512)
    model = build_model(cfg)
    B, S, L = 8, 512, 64
    params = jax.eval_shape(
        lambda k: model.init_params(k, BF16), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(B, S, BF16))
    abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)
    if chips == 1:
        one = SingleDeviceSharding(dev)
        p_sh = jax.tree_util.tree_map(lambda _: one, params)
        c_sh = jax.tree_util.tree_map(lambda _: one, cache)
        rep = one
        ctx = LayerCtx(abft=abft, cache_device=dev)
    else:
        mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
        p_sh = make_sharding(mesh, param_specs(cfg, params, mesh))
        c_sh = make_sharding(mesh, cache_specs(cfg, cache, mesh, B))
        rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
        ctx = LayerCtx(abft=abft, hints=make_hints(cfg, mesh))
    runner = ModelRunner(model, ctx)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            tree, shardings)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params, cache = placed(params, p_sh), placed(cache, c_sh)
    fault = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), jax.eval_shape(ModelFault.none))
    i32 = lambda *s: spec(s, jnp.int32)                  # noqa: E731
    keys = lambda n: spec((n, 2), jnp.uint32)            # noqa: E731
    if entry == "decode":
        args = (params, i32(B, 1), cache, i32(B), spec((B,), jnp.bool_),
                keys(B), None, fault)
    else:
        args = (params, i32(1, L), cache, i32(1), i32(1), keys(1), None,
                i32(1), spec((1,), jnp.bool_), fault)
    hlo = getattr(runner, entry).lower(*args).compile().as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    leaf = leaves[0]
    shard = leaf.sharding.shard_shape(leaf.shape)
    stored = Layout.from_pjrt_layout(
        dev.client.get_default_layout(leaf.dtype, shard, dev))
    dims = ",".join(map(str, shard))
    want = ",".join(map(str, stored.major_to_minor[::-1]))
    seen = set(re.findall(rf"\[{dims}\]\{{([\d,]+)", hlo))
    copies = re.findall(rf"= bf16\[{dims}\]\S* copy\(", hlo)
    relayout = chips == 4 and entry == "prefill_chunk"
    if not relayout:
        assert seen == {want}, (
            f"cache shard {shard} stored {{{want}}} but the program also "
            f"holds it in {seen - {want}}")
    assert len(copies) <= (2 if relayout else 1) * len(leaves), (
        f"{len(copies)} whole-cache copies of {len(leaves)} cache leaves")
    if chips == 4:
        full = ",".join(map(str, leaf.shape))
        assert f"[{full}]" not in hlo, "the sharded cache is gathered whole"
