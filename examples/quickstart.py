"""Quickstart: ABFT-protected matmuls in three lines, then a protected
model forward with fault injection + detection.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ABFTConfig,
    FaultSpec,
    GemmDims,
    protected_matmul,
    selection_report,
)

# ---------------------------------------------------------------- 1. one GEMM
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((64, 512)), jnp.bfloat16)
w = jnp.asarray(rng.standard_normal((512, 1024)), jnp.bfloat16)

y, check = protected_matmul(x, w)          # scheme auto-selected by AI vs CMR
print(f"1) clean GEMM: fault detected = {bool(check.flag)}")

# inject a soft error into the GEMM output -> detected.  On the fused
# block path the bit indexes the f32 accumulator (bits 23-30 = exponent);
# on the global path it indexes the output dtype.
y, check = protected_matmul(x, w, fault=FaultSpec.bitflip(row=3, col=17,
                                                          bit=28))
print(f"   bit-flipped GEMM: fault detected = {bool(check.flag)}")
assert bool(check.flag)

# ---------------------------------------------------------------- 2. selection
print("\n2) intensity-guided selection (paper §5.3):")
report = selection_report({
    "decode mlp (thin)": GemmDims(m=8, k=4096, n=14336),
    "prefill mlp (fat)": GemmDims(m=131072, k=4096, n=14336),
})
for r in report:
    print(f"   {r['layer']:20s} AI={r['ai']:9.1f} {r['bound']:9s} "
          f"-> {r['scheme']}")

# ------------------------------------------------------- 2b. the policy API
# selection_report above rides the legacy facade; the first-class surface
# is a ProtectionPolicy compiled into a ProtectionPlan (JSON-serializable
# deployment artifact with a cached per-step fast path):
from repro.core import (
    IntensityGuidedPolicy,
    ProtectionPlan,
    StepShape,
    TPU_V5E,
)

plan = ProtectionPlan.build(
    {"decode mlp (thin)": GemmDims(m=8, k=4096, n=14336),
     "prefill mlp (fat)": GemmDims(m=131072, k=4096, n=14336)},
    hw=TPU_V5E, policy=IntensityGuidedPolicy(),
    step_shape=StepShape(d_model=4096, d_ff=14336))
reloaded = ProtectionPlan.from_json(plan.to_json())
assert [e.selection.scheme_name for e in reloaded.entries] == \
    [e.selection.scheme_name for e in plan.entries]
print(f"\n2b) plan round-trip: {len(plan.entries)} layers, "
      f"decode-step scheme = {plan.for_step(8).scheme_name}")

# ------------------------------------------------- 2c. the coverage auditor
# a plan *claims* protection; the auditor *proves* it: trace the model's
# real prefill/decode entry points to jaxprs, walk every FLOP-carrying
# primitive, and check each one sits inside a registered scheme's dispatch
# scope — with the plan <-> trace site bijection as a second witness.
# CLI equivalent: python -m repro.launch.audit --config llama3.2-1b
from repro.analysis import audit_config

rep = audit_config("llama3.2-1b", phase="decode", check_flash=False)
assert rep.protected_fraction == 1.0 and rep.crosscheck.bijective
print(f"\n2c) coverage audit: protected={rep.protected_fraction:.2f}; "
      f"{rep.crosscheck.report()}")

# ---------------------------------------------------- 2d. observability
# the serving telemetry stack is dependency-free and usable standalone:
# a metrics registry (JSON export), a span tracer (jax.profiler events,
# recorded as Perfetto JSON when enabled), and the rolling fault-rate
# monitor that feeds adaptive protection (ROADMAP 5b).  The serve
# driver wires all three behind --metrics-out / --trace-out /
# --log-events.
from repro.obs import FaultRateMonitor, MetricsRegistry, Tracer

reg = MetricsRegistry()
detections = reg.counter("abft_faults_detected_total",
                         "ABFT checksum mismatches", labels=("scheme",))
detections.labels(scheme="global").inc()
lat = reg.histogram("serve_step_latency_seconds", "step wall time",
                    buckets=(0.001, 0.01, 0.1))
lat.observe(0.004)

tracer = Tracer()
with tracer.span("serve.decode", {"rows": 8}):
    with tracer.span("serve.decode.wait", {"what": "flag"}):
        pass
tracer.instant("scheme_flip", {"scheme": "global", "intensity": 42.0})

monitor = FaultRateMonitor(window=128)
monitor.observe(steps=1, tokens=8, detections=1, retries=1)
print("\n2d) telemetry:")
print(f"   metrics = {reg.names()}")
print(f"   trace events = {len(tracer.events)}, windowed detection "
      f"rate = {monitor.window_detection_rate:.3f}/step")

# ------------------------------------------- 2e. per-shard plans (mesh)
# tensor parallelism divides each GEMM's N (column-parallel) or K
# (row-parallel) by the mesh width, lowering every shard's arithmetic
# intensity — so the same layer on the same hardware can land on a
# DIFFERENT scheme once sharded.  Plan compilation is host-side: no
# devices needed to see the divergence (serving over a real mesh is
# ServeEngine(mesh=k); see README "Sharded serving").
from repro.configs import get_config, scaled_down
from repro.core.hardware import HardwareSpec
from repro.models import LayerCtx, ModelFault, build_model

cfg = scaled_down(get_config("llama3.2-1b"))
model = build_model(cfg)
shard_hw = HardwareSpec(        # CMR between full-width and 4-way-shard AI
    name="shard-flip", peak_flops=2.4e13, vpu_flops=1e11, hbm_bw=1e12,
    ici_bw=1e11, hbm_bytes=1 << 34, vmem_bytes=1 << 24,
    fixed_op_overhead_s=1e-7)
print("\n2e) per-shard protection plans (tensor parallel):")
per_width = {}
for tp in (1, 4):
    p = model.protection_plan(hw=shard_hw, phase="serve", n_tokens=64,
                              model_parallel=tp)
    per_width[tp] = {r["layer"]: r for r in p.report_rows()}
for layer, row in per_width[1].items():
    r4 = per_width[4][layer]
    mark = "  <- scheme flips" if row["scheme"] != r4["scheme"] else ""
    print(f"   {layer:9s} TP=1 ai={row['ai']:5.1f} {row['scheme']:8s} | "
          f"TP=4 ai={r4['ai']:5.1f} {r4['scheme']:8s}{mark}")
assert any(per_width[1][la]["scheme"] != per_width[4][la]["scheme"]
           for la in per_width[1])

# ------------------------------- 2f. fault campaigns + adaptive protection
# the one-shot fault above becomes a *process*: a seeded FaultModel
# Bernoulli-injects transient (or sticky permanent) faults every engine
# step, the engine's shadow-stream harness classifies each one as
# corrected / uncorrected / SDC / masked, and an ErrorAdaptivePolicy
# consumes the observed fault RATE to escalate protection at runtime
# (ROADMAP 5b/5c; benchmarks/fault_campaign.py runs the full sweep).
from repro.core import ErrorAdaptivePolicy, FaultModel, Scheme
from repro.serve.engine import Request, ServeEngine

print("\n2f) fault campaign + error-rate-adaptive escalation:")
qparams = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
qreqs = lambda: [Request(uid=i,                                 # noqa: E731
                         prompt=np.arange(1, 6 + i, dtype=np.int32),
                         max_new_tokens=5) for i in range(2)]
adaptive = ErrorAdaptivePolicy(IntensityGuidedPolicy(),
                               detection_threshold=0.05)
campaign = FaultModel(transient_rate=0.5, seed=1, layers=cfg.n_layers,
                      dtype=jnp.float32, magnitude=1e4)
clean_eng = ServeEngine(model, qparams, slots=2, max_len=64,
                        abft=ABFTConfig.from_policy(
                            IntensityGuidedPolicy(), use_pallas=False),
                        dtype=jnp.float32)
clean_streams = clean_eng.run(qreqs())
eng = ServeEngine(model, qparams, slots=2, max_len=64,
                  abft=ABFTConfig.from_policy(adaptive,
                                              use_pallas=False),
                  dtype=jnp.float32, fault_model=campaign)
streams = eng.run(qreqs())
s = eng.stats
print(f"   injected={s.faults_injected} corrected={s.faults_corrected} "
      f"uncorrected={s.faults_uncorrected} sdc={s.sdc_faults} "
      f"masked={s.masked_faults}")
print(f"   escalations={s.protection_escalations} "
      f"(level {eng.protection_level}: the observed detection rate "
      f"crossed {adaptive.detection_threshold})")
for entry in s.injection_log[:3]:
    print(f"   step {entry['engine_step']:2d} {entry['phase']:8s} "
          f"L{entry['layer']} {entry['site']:8s} -> {entry['outcome']}")
assert s.faults_injected > 0 and s.sdc_faults == 0
assert s.protection_escalations >= 1
assert streams == clean_streams          # recovery stayed transparent

# ----------------------------- 2g. speculative decoding flips the scheme
# spec_decode speculates K drafts per slot and scores all K+1 positions
# in ONE jitted verify call — so a decode step's token dimension grows
# from `slots` to sum(k_i + 1).  On hardware whose scheme crossover sits
# between the two (here ~18 tokens for this f32 plan: 4-slot plain
# decode = 4 tokens, full K=4 verify window = 20), speculation alone
# flips the per-step scheme — the paper's intensity decision reacting
# to the serving optimization.  Streams stay byte-identical: greedy
# verify provably reproduces the unsped stream (see
# repro/serve/spec_decode.py), so draft quality only buys throughput.
flip_hw = HardwareSpec(
    name="flip", peak_flops=1e10, vpu_flops=2.6e8, hbm_bw=1e9,
    ici_bw=1e9, hbm_bytes=1 << 30, vmem_bytes=1 << 20,
    fixed_op_overhead_s=1e-6)
spec_reqs = lambda: [Request(uid=i,                             # noqa: E731
                             prompt=np.tile(np.arange(3, 7 + i % 2,
                                                      dtype=np.int32),
                                            16)[:21 + 2 * i],
                             max_new_tokens=14 + i % 3)
                     for i in range(4)]
spec_abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False,
                       hardware=flip_hw)
print("\n2g) speculative decoding (K-sweep on scheme-flip hardware):")
base_eng = ServeEngine(model, qparams, slots=4, max_len=64,
                       abft=spec_abft, dtype=jnp.float32)
base = base_eng.run(spec_reqs())
for k in (1, 4):
    seng = ServeEngine(model, qparams, slots=4, max_len=64,
                       abft=spec_abft, dtype=jnp.float32,
                       spec_decode="ngram", draft_len=k)
    sout = seng.run(spec_reqs())
    assert sout == base                  # byte-identical greedy streams
    st = seng.stats
    schemes = sorted({e["scheme"] for e in st.selection_trace
                      if e["decode"] and not e["prefill"]})
    rate = st.draft_accepted / max(st.draft_proposed, 1)
    print(f"   K={k}: accept={rate:.2f} verify-window schemes={schemes}")
    if k == 4:
        assert "global" in schemes       # K=4 window crossed the CMR
print(f"   plan.for_step:  4 tokens -> "
      f"{base_eng.plan.for_step(4).scheme_name},  20 tokens -> "
      f"{base_eng.plan.for_step(20).scheme_name}")

# ---------------------------------------------------------------- 3. a model
params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
ctx = LayerCtx(abft=ABFTConfig.from_policy(IntensityGuidedPolicy(),
                                           use_pallas=False))
batch = {"tokens": jnp.ones((2, 16), jnp.int32)}

out = model.forward(params, batch, ctx)
print(f"\n3) model forward: logits {out.logits.shape}, "
      f"fault detected = {bool(out.flag)}")

bad_ctx = LayerCtx(
    abft=ctx.abft,
    fault=ModelFault.at(1, "mlp_down", FaultSpec.value(0, 3, 1e4)))
out = model.forward(params, batch, bad_ctx)
print(f"   with injected layer fault: detected = {bool(out.flag)}")
