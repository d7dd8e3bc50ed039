"""Integration: the fused-ABFT flash-attention backend is a drop-in for
the XLA chunked path inside a full model, and a real sharded train step
executes end-to-end on an 8-device host mesh (subprocess)."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, FaultSpec, Scheme
from repro.models import LayerCtx, ModelFault, build_model


def test_flash_backend_matches_chunked():
    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = {"tokens": jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % 50}
    base = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)
    out_x = model.forward(params, batch, LayerCtx(abft=base))
    import dataclasses

    flash = dataclasses.replace(base, flash_attention=True)
    out_f = model.forward(params, batch, LayerCtx(abft=flash))
    np.testing.assert_allclose(
        np.asarray(out_x.logits), np.asarray(out_f.logits),
        rtol=2e-3, atol=2e-3)
    assert not bool(out_f.flag)


def test_flash_backend_detects_projection_fault():
    """Layer-GEMM faults still flag with the flash backend active."""
    import dataclasses

    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    abft = dataclasses.replace(
        ABFTConfig(scheme=Scheme.AUTO, use_pallas=False),
        flash_attention=True)
    ctx = LayerCtx(
        abft=abft,
        fault=ModelFault.at(1, "attn_out", FaultSpec.value(0, 2, 1e4)))
    out = model.forward(params, batch, ctx)
    assert bool(out.flag)


_DIST_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, Scheme
from repro.distributed import sharding as shd
from repro.distributed.mesh import build_mesh
from repro.models import build_model
from repro.models.layers import ShardingHints
from repro.train import OptConfig, TrainConfig, init_opt_state, make_train_step

cfg = scaled_down(get_config("qwen2-moe-a2.7b"), n_layers=2, n_experts=4,
                  d_model=64, vocab_size=128)
model = build_model(cfg)
mesh = build_mesh(model=4, data=2)
hints = ShardingHints(dp=("data",), dp_size=2, moe_mode="ep")
abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)
tcfg = TrainConfig(opt=OptConfig(lr=1e-3))
params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
opt = init_opt_state(params, tcfg.opt)
p_spec = shd.param_specs(cfg, params, mesh)
o_spec = shd.opt_state_specs(cfg, opt, mesh)
p_sh = shd.make_sharding(mesh, p_spec)
o_sh = shd.make_sharding(mesh, o_spec)
params = jax.device_put(params, p_sh)
opt = jax.device_put(opt, o_sh)
batch = {
    "tokens": jnp.ones((4, 16), jnp.int32),
    "labels": jnp.ones((4, 16), jnp.int32),
}
b_sh = shd.make_sharding(mesh, {k: P(("data",), None) for k in batch})
batch = jax.device_put(batch, b_sh)
step = make_train_step(model, abft, tcfg, hints=hints)
with mesh:
    jstep = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                    out_shardings=(p_sh, o_sh, None))
    losses = []
    for _ in range(3):
        params, opt, metrics = jstep(params, opt, batch)
        losses.append(float(metrics["loss"]))
print(json.dumps({
    "losses": losses,
    "n_devices": len(jax.devices()),
    "flag": bool(metrics["abft_flag"]),
}))
"""


def test_sharded_train_step_executes_on_8_devices():
    """Not just compile: a DP+TP+EP-sharded MoE train step RUNS on an
    8-device host mesh; loss decreases and no ABFT flags trip."""
    res = subprocess.run(
        [sys.executable, "-c", _DIST_TRAIN], capture_output=True, text=True,
        timeout=900, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root"})
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert not out["flag"]
    assert all(np.isfinite(x) for x in out["losses"])
    assert out["losses"][-1] < out["losses"][0]
