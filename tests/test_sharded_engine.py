"""Sharded serving: per-shard protection-plan divergence and mesh
stream equivalence (ISSUE 8 acceptance surface).

Host-side (always runs, one device):
  * ``model_parallel=k`` divides the plan's GEMM dims and — on a
    crafted HardwareSpec whose CMR sits between the TP=1 and TP=4
    arithmetic intensities — SELECTS A DIFFERENT SCHEME per shard:
    the paper's intensity-guided decision re-made for post-sharding
    shapes.
  * plan JSON round-trips ``model_parallel``; ``plan_row`` telemetry
    instants export the per-shard selections.
  * a ``MeshExecutor`` over a 1-wide mesh is byte-identical to the
    local executor.

Multi-device (``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
skipped when the host exposes fewer devices — the sharded-smoke CI job
runs them):
  * greedy streams are byte-identical between mesh=1 and mesh=k for
    k in {2, 4} — dense, paged, chunked + prefix-shared, and under
    injected prefill/decode faults with retry and hard-fault eviction.

bf16 everywhere: at these small widths per-device partial GEMMs
accumulate in f32 and round below bf16 output precision, so TP's psum
reordering does not perturb the streams (full-f32 models can differ in
the last ulp).  At full width on a TPU a near-tie in the argmax can
flip; ``chip_smoke.py --mesh 4`` compares the logits there instead."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core.faults import FaultSpec
from repro.core.hardware import HardwareSpec
from repro.core.protected import ABFTConfig
from repro.core.schemes import Scheme
from repro.models import ModelFault, build_model
from repro.obs import EngineTelemetry
from repro.serve.engine import RecoveryPolicy, Request, ServeEngine
from repro.serve.executor import LocalExecutor, MeshExecutor

N_DEV = len(jax.devices())

# CMR = 24 FLOPs/byte sits between the smoke model's TP=4 intensities
# (all <= 21.3) and its TP=1 mlp/lm_head intensities (25.6 / 28.4): the
# full-model mlp/lm_head shapes are compute-bound, every 4-way shard is
# bandwidth-bound.  The slow VPU + cheap fixed ops tilt the overhead
# model so global ABFT's dispatch cost amortizes over the full-width
# GEMMs but not over the 4x-narrower shards — the crafted point where
# the intensity-guided decision lands differently per shard width.
SHARD_HW = HardwareSpec(
    name="shard-flip", peak_flops=2.4e13, vpu_flops=1e11, hbm_bw=1e12,
    ici_bw=1e11, hbm_bytes=1 << 34, vmem_bytes=1 << 24,
    fixed_op_overhead_s=1e-7)


@pytest.fixture(scope="module")
def setup():
    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    return cfg, model, params


def _reqs(cfg, n=6, seed=0, new_tokens=5):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(
                        1, cfg.vocab_size,
                        size=rng.integers(4, 20)).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n)]


# ------------------------------------------------- per-shard plan (host)
class TestShardedPlan:
    def test_tp_divides_gemm_dims(self, setup):
        cfg, model, _ = setup
        p1 = model.protection_plan(hw=SHARD_HW, phase="serve",
                                   n_tokens=64, model_parallel=1)
        p4 = model.protection_plan(hw=SHARD_HW, phase="serve",
                                   n_tokens=64, model_parallel=4)
        r1 = {r["layer"]: r for r in p1.report_rows()}
        r4 = {r["layer"]: r for r in p4.report_rows()}
        assert r4["attn.q"]["n"] * 4 == r1["attn.q"]["n"]     # column ||
        assert r4["attn.o"]["k"] * 4 == r1["attn.o"]["k"]     # row ||
        assert r4["mlp.up"]["n"] * 4 == r1["mlp.up"]["n"]
        assert r4["lm_head"]["n"] * 4 == r1["lm_head"]["n"]
        for site in r1:
            assert r4[site]["ai"] <= r1[site]["ai"]

    def test_scheme_diverges_between_shard_widths(self, setup):
        """THE acceptance assertion: on SHARD_HW, TP=4 selects a
        different ABFT scheme than TP=1 for at least one layer."""
        cfg, model, _ = setup
        p1 = model.protection_plan(hw=SHARD_HW, phase="serve",
                                   n_tokens=64, model_parallel=1)
        p4 = model.protection_plan(hw=SHARD_HW, phase="serve",
                                   n_tokens=64, model_parallel=4)
        r1 = {r["layer"]: r for r in p1.report_rows()}
        r4 = {r["layer"]: r for r in p4.report_rows()}
        diverged = [s for s in r1
                    if r1[s]["scheme"] != r4[s]["scheme"]]
        assert diverged                       # >= 1 layer flips scheme
        for s in diverged:
            assert (r1[s]["scheme"], r1[s]["bound"]) == \
                ("global", "compute")
            assert (r4[s]["scheme"], r4[s]["bound"]) == \
                ("block_1s", "bandwidth")
        # and narrow shards keep schemes where both sit in one regime
        assert r1["attn.k"]["scheme"] == r4["attn.k"]["scheme"]

    def test_plan_json_roundtrips_model_parallel(self, setup):
        from repro.core.policy import ProtectionPlan
        cfg, model, _ = setup
        p4 = model.protection_plan(hw=SHARD_HW, phase="serve",
                                   n_tokens=64, model_parallel=4)
        assert p4.model_parallel == 4
        rt = ProtectionPlan.from_json(p4.to_json())
        assert rt.model_parallel == 4
        assert [r["scheme"] for r in rt.report_rows()] == \
            [r["scheme"] for r in p4.report_rows()]

    def test_engine_plan_rows_in_telemetry(self, setup):
        cfg, model, params = setup
        tel = EngineTelemetry(trace=True)
        abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False,
                          hardware=SHARD_HW)
        eng = ServeEngine(model, params, slots=2, max_len=32, abft=abft,
                          dtype=jnp.bfloat16, telemetry=tel)
        rows = [e for e in tel.tracer.events if e["name"] == "plan_row"]
        assert len(rows) == len(eng.plan.report_rows())
        for e in rows:
            assert e["args"]["model_parallel"] == 1
            assert "scheme" in e["args"] and "ai" in e["args"]


# ------------------------------------------------------- executor layer
class TestExecutors:
    def test_mesh_executor_rejects_meshless_axis(self, setup):
        cfg, model, params = setup
        from jax.sharding import Mesh
        m = Mesh(np.array(jax.devices()[:1]), ("x",))
        with pytest.raises(ValueError, match="model"):
            MeshExecutor(model, params, mesh=m, dtype=jnp.bfloat16)

    def test_mesh1_executor_matches_local(self, setup):
        cfg, model, params = setup
        local = LocalExecutor(model, params, dtype=jnp.bfloat16)
        sharded = MeshExecutor(model, params, mesh=1, dtype=jnp.bfloat16)
        assert sharded.model_parallel == 1
        assert local.protection_plan(ABFTConfig(), slots=4).to_json() == \
            sharded.protection_plan(ABFTConfig(), slots=4).to_json()

    def test_engine_mesh1_streams_match_local(self, setup):
        cfg, model, params = setup
        ref = ServeEngine(model, params, slots=3, max_len=64,
                          dtype=jnp.bfloat16).run(_reqs(cfg))
        got = ServeEngine(model, params, slots=3, max_len=64,
                          dtype=jnp.bfloat16, mesh=1).run(_reqs(cfg))
        assert got == ref


# ------------------------------------------------ mesh stream equality
@pytest.mark.parametrize("k", [2, 4])
class TestMeshEquivalence:
    def _skip(self, k):
        if N_DEV < k:
            pytest.skip(f"needs {k} devices, have {N_DEV} (set XLA_FLAGS="
                        f"--xla_force_host_platform_device_count=8)")

    def test_dense_streams_byte_identical(self, setup, k):
        self._skip(k)
        cfg, model, params = setup
        ref = ServeEngine(model, params, slots=3, max_len=64,
                          dtype=jnp.bfloat16).run(_reqs(cfg))
        eng = ServeEngine(model, params, slots=3, max_len=64,
                          dtype=jnp.bfloat16, mesh=k)
        assert eng.model_parallel == k
        assert eng.run(_reqs(cfg)) == ref

    def test_paged_chunked_prefix_streams_byte_identical(self, setup, k):
        self._skip(k)
        cfg, model, params = setup
        kw = dict(slots=3, max_len=64, dtype=jnp.bfloat16,
                  cache_kind="paged", block_size=8, prefix_sharing=True,
                  chunk_tokens=12)
        # shared prefixes across requests so COW + the prefix index
        # engage on both engines
        reqs = _reqs(cfg, n=6, seed=3)
        for r in reqs[3:]:
            r.prompt = np.concatenate(
                [reqs[0].prompt[:12], r.prompt]).astype(np.int32)
        ref_eng = ServeEngine(model, params, **kw)
        ref = ref_eng.run([Request(r.uid, r.prompt.copy(),
                                   r.max_new_tokens) for r in reqs])
        eng = ServeEngine(model, params, mesh=k, **kw)
        got = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                       for r in reqs])
        assert got == ref
        assert eng.stats.prefix_tokens_shared == \
            ref_eng.stats.prefix_tokens_shared > 0
        assert eng.stats.prefill_chunks == ref_eng.stats.prefill_chunks
        assert eng.pool.blocks_used == 0        # drained clean

    def test_streams_match_under_faults_with_retry(self, setup, k):
        self._skip(k)
        cfg, model, params = setup
        fault = ModelFault.at(0, "mlp_down", FaultSpec.value(0, 1, 1e5))
        kw = dict(slots=3, max_len=64, dtype=jnp.bfloat16,
                  cache_kind="paged", block_size=8)
        outs, engines = [], []
        for mesh in (None, k):
            eng = ServeEngine(model, params, mesh=mesh, **kw)
            outs.append(eng.run(_reqs(cfg), fault_at=(2, fault),
                                admit_fault_at=(1, fault)))
            engines.append(eng)
        assert outs[1] == outs[0]
        for eng in engines:
            assert eng.stats.faults_detected >= 2    # decode AND prefill
            assert eng.stats.retries >= 2
            assert eng.stats.hard_faults == 0        # recovery succeeded

    def test_hard_fault_eviction_matches(self, setup, k):
        self._skip(k)
        cfg, model, params = setup
        fault = ModelFault.at(0, "mlp_down", FaultSpec.value(0, 1, 1e5))
        kw = dict(slots=2, max_len=64, dtype=jnp.bfloat16,
                  policy=RecoveryPolicy(max_retries=0,
                                        evict_on_hard_fault=True))
        outs, engines = [], []
        for mesh in (None, k):
            eng = ServeEngine(model, params, mesh=mesh, **kw)
            reqs = _reqs(cfg, n=4, seed=5)
            outs.append((eng.run(reqs, fault_at=(1, fault)),
                         {r.uid: r.error for r in reqs}))
            engines.append(eng)
        assert outs[1] == outs[0]
        for eng in engines:
            assert eng.stats.hard_faults == 1
            assert eng.stats.evictions >= 1


# ----------------------------------------------- sharded telemetry plan
@pytest.mark.skipif(N_DEV < 2, reason="needs 2 devices")
def test_sharded_plan_rows_in_telemetry(setup):
    cfg, model, params = setup
    tel = EngineTelemetry(trace=True)
    abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False,
                      hardware=SHARD_HW)
    eng = ServeEngine(model, params, slots=2, max_len=32, abft=abft,
                      dtype=jnp.bfloat16, mesh=2, telemetry=tel)
    rows = [e for e in tel.tracer.events if e["name"] == "plan_row"]
    assert rows
    for e in rows:
        assert e["args"]["model_parallel"] == 2
    # the exported rows ARE the per-shard plan: dims match the TP=2 plan
    assert {e["args"]["layer"] for e in rows} == \
        {r["layer"] for r in eng.plan.report_rows()}


# ------------------------------------- kernel schemes per shard (shard_map)
_PER_SHARD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.core.faults import FaultSpec
from repro.core.policy import FixedPolicy
from repro.core.protected import ABFTConfig, protected_matmul
from repro.core.schemes import Scheme
from repro.distributed.mesh import build_mesh

one = ABFTConfig.from_policy(FixedPolicy(Scheme.BLOCK_1S))
tp4 = dataclasses.replace(one, mesh=build_mesh(model=4))
ks = jax.random.split(jax.random.PRNGKey(0), 4)
out = {}
# attn.q is column-parallel (n sharded), attn.o row-parallel (k sharded)
for site, (k, n) in (("attn.q", (64, 256)), ("attn.o", (256, 64))):
    x = jax.random.normal(ks[0], (2, 8, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * k ** -0.5
    fault = FaultSpec.value(5, n - 7, 1e3)      # last shard's columns
    res = {}
    for name, cfg in (("one", one), ("tp4", tp4)):
        f = jax.jit(lambda x, w, flt, cfg=cfg: protected_matmul(
            x, w, cfg, fault=flt, site=site))
        y, c = f(x, w, FaultSpec.none())
        yf, cf = f(x, w, fault)
        hlo = f.lower(x, w, fault).as_text()
        res[name] = (np.asarray(y), bool(c.flag), np.asarray(yf),
                     bool(cf.flag), "shard_map" in hlo or "sdy.manual" in hlo)
    y1, flag1, yf1, fflag1, _ = res["one"]
    y4, flag4, yf4, fflag4, manual = res["tp4"]
    out[site] = {
        "y_close": bool(np.allclose(y4, y1, rtol=1e-5, atol=1e-5)),
        "clean_flags": [flag1, flag4], "fault_flags": [fflag1, fflag4],
        "fault_lands_same": bool(np.allclose(yf4, yf1, rtol=1e-5,
                                             atol=1e-3)),
        "per_shard": manual,
    }
print(json.dumps(out))
"""


def test_kernel_schemes_run_per_shard_on_a_tp_mesh():
    """On a 4-wide model axis a kernel scheme runs under shard_map on the
    column- and row-parallel sites: same output as one device, clean
    runs unflagged, and an injected fault in the last shard's columns
    flagged and landing on the same output element."""
    res = subprocess.run(
        [sys.executable, "-c", _PER_SHARD], capture_output=True,
        text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for site in ("attn.q", "attn.o"):
        r = out[site]
        assert r["per_shard"], site
        assert r["y_close"], site
        assert r["clean_flags"] == [False, False], site
        assert r["fault_flags"] == [True, True], site
        assert r["fault_lands_same"], site


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-x", "-q"]))
