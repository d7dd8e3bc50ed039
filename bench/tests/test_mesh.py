"""A cell on four chips, rehearsed on four virtual CPU devices in a
child process (the device count is fixed when JAX starts): for each
configuration, the weights made sharded are those made on one device,
their specs are the program's own sharding rules and the engine takes
them without a copy; and the harness, with its look for a chip skipped,
comes out correct for a sound engine and not correct for each fault a
sharded serving cell can have.  The fp8 control is checked at the same
size."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.spec import ROOT
from bench.tests.test_faults import FAULTS

CELL = "stablelm-2-1.6b.chat"
CONFIGS = ["stablelm-2-1.6b.chat", "qwen3-14b-pp4.docs"]


def _exchange_left_out(engine):
    """Each row-parallel GEMM (o and down) keeps the partial sum of the
    first device's rows alone, as that device would see it if the
    all-reduce after the GEMM were left out."""
    lp = engine.executor.params["segments"][0]["pos0"]
    chips = engine.executor.model_parallel

    def first_shard(w):
        k = w.shape[-2]
        keep = (jnp.arange(k) < k // chips)[:, None]
        return jax.device_put(jnp.where(keep, w, 0), w.sharding)

    lp["mixer"]["wo"] = first_shard(lp["mixer"]["wo"])
    lp["ffn"]["down"] = first_shard(lp["ffn"]["down"])


MESH_FAULTS = {**FAULTS, "exchange_left_out": _exchange_left_out}

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import jax
import numpy as np
from bench import correct, harness, program, spec, traffic, weights
from bench.tests.small import small_cell
from bench.tests.test_mesh import CELL, CONFIGS, MESH_FAULTS
from repro.distributed.sharding import param_specs


def pointers(tree):
    return [sorted(s.data.unsafe_buffer_pointer()
                   for s in a.addressable_shards)
            for a in jax.tree_util.tree_leaves(tree)]


out = {"sharded_equal": {}, "sharded_spread": {}, "specs_equal": {},
       "placed_without_copy": {}}
for name in CONFIGS:
    cell = small_cell(name, chips=4)
    cfg = cell.config
    devs, _ = harness.device_info(cell, False)
    mesh = harness.cell_mesh(devs)
    one = weights.make(cfg, 2**33 + 5)
    many = weights.make(cfg, 2**33 + 5, mesh)
    out["sharded_equal"][name] = {
        n: bool(np.array_equal(np.asarray(one[n]), np.asarray(many[n])))
        for n in one}
    out["sharded_spread"][name] = {n: len(many[n].sharding.device_set)
                                   for n in many}
    engine, _, _ = program.build(cfg, cell.config_name, cell.settings,
                                 many, 1, mesh)
    nested = program.params(engine.model, cfg, many)
    want = jax.tree_util.tree_leaves(
        param_specs(engine.model.cfg, nested, mesh),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    got = [a.sharding.spec for a in jax.tree_util.tree_leaves(nested)]
    out["specs_equal"][name] = [len(got) == len(want)] + [
        g == w for g, w in zip(got, want)]
    out["placed_without_copy"][name] = pointers(
        engine.executor.params) == pointers(nested)
    del engine

cell = small_cell(CELL, chips=4)
cfg = cell.config
runs = {}
for fault in sorted(MESH_FAULTS):
    res = harness.run(cell.name, 2**31 + 17, 2.0, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      cell=cell, engine_hook=MESH_FAULTS[fault])
    runs[fault] = {"correct": res["correct"], "checks": res["checks"],
                   "metrics": sorted(res["metrics"]),
                   "device": res["device"], "last": list(res)[-1]}
out["runs"] = runs

drv, w, _, _ = harness.setup(cell, 99, require_tpu=False)
items = traffic.stream(cell.mix, 99, cfg["vocab_size"],
                       rate=cell.settings["rate_per_s"])
win = drv.window(items, 2.0, loop="open")
drv.drain()
done = [r for r in win.requests if r.done and r.generated]
chosen = correct.sample(done, cell.settings["check_tokens"], 99)
ref = spec.reference(cfg["architecture"])
out["program_gap"] = float(correct.gaps(ref, w, cfg, chosen).max())
ctrl = correct.gaps(ref, w, cfg, chosen, precision="fp8")
out["control_gap"] = float(ctrl.max())
out["control_nonzero"] = int(np.count_nonzero(ctrl))
out["limit"] = cell.settings["max_logit_gap"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    res = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=1500, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{ROOT / 'src'}"})
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CONFIGS)
def test_sharded_weights_equal_one_device(mesh_run, name):
    equal = mesh_run["sharded_equal"][name]
    assert equal and all(equal.values()), equal
    # the projections and the vocabulary are spread over the four
    assert mesh_run["sharded_spread"][name]["wq"] == 4
    assert mesh_run["sharded_spread"][name]["lm_head"] == 4


@pytest.mark.parametrize("name", CONFIGS)
def test_specs_are_the_programs(mesh_run, name):
    assert all(mesh_run["specs_equal"][name])


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_takes_the_weights_without_a_copy(mesh_run, name):
    assert mesh_run["placed_without_copy"][name]


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_correct_catches_faults_on_a_mesh(mesh_run, fault):
    run = mesh_run["runs"][fault]
    assert run["correct"] is (fault == "sound"), run["checks"]
    assert run["last"] == "checks"
    assert run["metrics"] == ["itl_mean_ms", "setup_s", "ttft_p50_ms"]
    dev = run["device"]
    assert dev["count"] == 4 and len(dev["per_device"]) == 4
    assert dev["memory_peak_bytes"] == max(
        d["memory_peak_bytes"] for d in dev["per_device"])


def test_fp8_control_fails_the_limit_on_a_mesh(mesh_run):
    """The control reads at least three times the program's widest gap,
    and above the limit."""
    assert mesh_run["control_gap"] >= max(3 * mesh_run["program_gap"],
                                          mesh_run["limit"])
    assert mesh_run["control_nonzero"] > 0
