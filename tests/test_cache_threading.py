"""The KV cache rides the layer scan as carry, and each layer writes only
its new rows into its own entry of the stacked leaves.

* structure — in the runner's decode, prefill-chunk and verify programs
  (dense and paged caches) no ``scan`` takes a cache-shaped array among
  its xs or returns one among its ys: threading the cache back through
  xs / ys would copy every layer out of the stack and back each call;
* placement — after one decode step every layer's entry differs from
  the old cache exactly at ``[layer, b, pos[b]]``, in every layer (the
  classic slip of a carry refactor writes every layer into entry 0),
  including single-unit segments that run unscanned at index 0.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jax_core

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, Scheme
from repro.models import ModelFault, build_model
from repro.models.layers import LayerCtx
from repro.serve.runner import ModelRunner

ABFT = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)
SLOTS, MAX_LEN, BLOCK, CHUNK, WINDOW = 3, 40, 8, 16, 4


def _scans(jaxpr):
    """Every scan equation in ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for item in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(item, jax_core.ClosedJaxpr):
                    yield from _scans(item.jaxpr)
                elif isinstance(item, jax_core.Jaxpr):
                    yield from _scans(item)


@pytest.fixture(scope="module")
def llama():
    model = build_model(scaled_down(get_config("llama3.2-1b"), n_layers=3))
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    return model, params


def _program(model, params, kind, entry):
    """(jitted entry point, its arguments) at small serving shapes."""
    runner = ModelRunner(model, LayerCtx(abft=ABFT))
    i32 = lambda *xs: jnp.asarray(np.array(xs, np.int32))  # noqa: E731
    fault = ModelFault.none()
    if kind == "paged":
        width = MAX_LEN // BLOCK
        cache = model.init_paged_cache(SLOTS, SLOTS * width, BLOCK,
                                       dtype=jnp.float32)
        tables = jnp.arange(SLOTS * width, dtype=jnp.int32).reshape(
            SLOTS, width)
    else:
        cache = model.init_cache(SLOTS, MAX_LEN, dtype=jnp.float32)
        tables = None
    keys = jax.random.split(jax.random.PRNGKey(1), SLOTS)
    pos = i32(3, 9, 17)
    mask = jnp.ones((SLOTS,), bool)
    if entry == "decode":
        return runner.decode, (params, jnp.ones((SLOTS, 1), jnp.int32),
                               cache, pos, mask, keys, tables, fault)
    if entry == "verify":
        return runner.verify, (params, jnp.ones((SLOTS, WINDOW), jnp.int32),
                               cache, pos, mask, i32(4, 2, 1), keys, tables,
                               fault)
    rows = tables[:1] if tables is not None else None
    return runner.prefill_chunk, (
        params, jnp.ones((1, CHUNK), jnp.int32), cache, i32(1), i32(11),
        keys[:1], rows, i32(8), jnp.ones((1,), bool), fault)


@pytest.mark.parametrize("entry", ["decode", "prefill_chunk", "verify"])
@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_scan_carries_cache_not_xs_ys(llama, kind, entry):
    model, params = llama
    fn, args = _program(model, params, kind, entry)
    cache = args[2]
    cache_shapes = set()
    for leaf in jax.tree_util.tree_leaves(cache):
        cache_shapes |= {tuple(leaf.shape), tuple(leaf.shape[1:])}
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    scans = list(_scans(jaxpr))
    assert scans, "the layer stack should run as a scan"
    carried = set()
    for eqn in scans:
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = eqn.invars[nc + nk:]
        ys = eqn.outvars[nk:]
        for v in list(xs) + list(ys):
            assert tuple(v.aval.shape) not in cache_shapes, (
                f"scan threads a cache-shaped {v.aval.shape} through its "
                f"xs / ys")
        carried |= {tuple(v.aval.shape) for v in eqn.invars[nc:nc + nk]}
    assert carried & cache_shapes, "the cache should ride a scan's carry"


def _random_cache(cache, key):
    leaves, tree = jax.tree_util.tree_flatten(cache)
    ks = jax.random.split(key, len(leaves))
    return tree.unflatten([jax.random.normal(k, a.shape, a.dtype)
                           for k, a in zip(ks, leaves)])


@pytest.mark.parametrize("cfg_name,n_layers,first_dense", [
    ("llama3.2-1b", 3, 0),      # one scanned segment of 3
    ("qwen2-moe-a2.7b", 13, 1),  # seg_plan rule (b): 1 dense + 12 MoE
    ("llama3.2-1b", 1, 0),      # a one-layer model: unscanned only
], ids=["scan3", "dense_then_moe", "one_layer"])
def test_decode_writes_only_new_rows(cfg_name, n_layers, first_dense):
    cfg = scaled_down(get_config(cfg_name), n_layers=n_layers)
    if first_dense:
        cfg = dataclasses.replace(cfg, first_dense_layers=first_dense)
    model = build_model(cfg)
    if first_dense:
        assert [s.repeats for s in model.plan] == [1, n_layers - 1]
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    old = _random_cache(model.init_cache(SLOTS, MAX_LEN, jnp.float32),
                        jax.random.PRNGKey(3))
    pos = jnp.asarray([0, 13, MAX_LEN - 1], jnp.int32)
    ctx = LayerCtx(abft=ABFT)
    _, new, _ = jax.jit(lambda *a: model.decode(*a, ctx))(
        params, jnp.asarray([[5], [7], [9]], jnp.int32), old, pos)
    expect = np.zeros((SLOTS, MAX_LEN), bool)
    expect[np.arange(SLOTS), np.asarray(pos)] = True
    n_entries = 0
    for a, b in zip(jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(new)):
        changed = np.asarray(a != b).reshape(a.shape[:3] + (-1,)).any(-1)
        for layer in range(a.shape[0]):
            np.testing.assert_array_equal(
                changed[layer], expect,
                err_msg=f"entry {layer} of a {a.shape} leaf")
            n_entries += 1
    assert n_entries == 2 * n_layers       # k and v of every layer
