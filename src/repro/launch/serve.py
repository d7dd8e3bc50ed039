"""Production serving driver: continuous batching + ABFT recovery stats.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --scale smoke --requests 8 --new-tokens 16 [--inject-faults] \
      [--fault-rate 0.2 --fault-kind transient --adaptive] \
      [--metrics-out m.json] [--trace-out t.json] [--log-events]

Fault-campaign flags: ``--fault-rate`` attaches a seeded ``FaultModel``
(continuous Bernoulli-per-step injection; ``--fault-kind permanent``
makes faults sticky across steps until ``--fault-duration`` expires),
and every injected fault is classified by the engine's shadow-stream
harness as corrected / uncorrected / SDC / masked.  ``--adaptive``
wraps the base policy in an ``ErrorAdaptivePolicy`` that escalates to
``global`` protection when the observed detection rate crosses
``--escalate-threshold`` and de-escalates with hysteresis when quiet.

Telemetry flags (repro/obs): ``--metrics-out`` writes the metrics
snapshot + fault-rate surface + final engine stats as one JSON artifact
(``benchmarks/check_telemetry_schema.py`` validates it);
``--trace-out`` writes the engine's host-phase spans (``serve.step``,
``serve.decode.wait``, ... — the same spans any ``jax.profiler`` trace
of the run holds) and instants as Chrome-trace/Perfetto JSON on the
tracer's own clock (load it at https://ui.perfetto.dev); ``--log-events``
streams every trace event as a JSON line to stderr while serving.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, scaled_down
from repro.core.faults import FaultModel, FaultSpec
from repro.core.hardware import on_tpu
from repro.core.policy import (
    ErrorAdaptivePolicy,
    FixedPolicy,
    IntensityGuidedPolicy,
)
from repro.core.protected import ABFTConfig
from repro.core.schemes import Scheme
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelFault, build_model
from repro.obs import ENGINE_COUNTERS, EngineTelemetry
from repro.runtime.heartbeat import HeartbeatMonitor
from repro.serve.engine import RecoveryPolicy, Request, ServeEngine


def _chunk_tokens(v: str):
    """--chunk-tokens value: an int budget or 'auto' (roofline-tuned)."""
    if str(v).lower() == "auto":
        return "auto"
    return int(v)


def _draft_len(v: str):
    """--draft-len value: an int K or 'auto' (roofline-tuned)."""
    if str(v).lower() == "auto":
        return "auto"
    return int(v)


def serving_dtype():
    """bfloat16 on a TPU (the chip's precision); float32 elsewhere, so
    CPU runs and tests keep their f32 numerics."""
    return jnp.bfloat16 if on_tpu() else jnp.float32


def abft_config(mode: str, *, adaptive: bool = False,
                escalate_threshold: float = 0.05) -> ABFTConfig:
    """--abft mode -> config.  Block schemes run the compiled kernel on a
    TPU and the jnp emulation on CPU; the hardware spec is the device's
    (``ABFTConfig.hw``)."""
    if mode == "off":
        return ABFTConfig.off()
    base = (IntensityGuidedPolicy() if mode == "auto"
            else FixedPolicy(Scheme(mode)))
    if adaptive:
        base = ErrorAdaptivePolicy(
            base, detection_threshold=escalate_threshold)
    return ABFTConfig.from_policy(base, use_pallas=on_tpu())


# the --inject-faults site: one value fault in layer 0's MLP down
# projection, armed from engine step 3
INJECT_STEP = 3


def injected_fault() -> ModelFault:
    return ModelFault.at(0, "mlp_down", FaultSpec.value(0, 1, 1e5))


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="llama3.2-1b")
    ap.add_argument("--scale", choices=["full", "smoke"], default="smoke")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--abft", default="auto",
                    choices=["auto", "global", "block_1s", "off"])
    ap.add_argument("--inject-faults", action="store_true")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-step Bernoulli fault probability: attaches "
                         "a seeded FaultModel for continuous campaign "
                         "injection (0 = no campaign)")
    ap.add_argument("--fault-kind", default="transient",
                    choices=["transient", "permanent"],
                    help="campaign fault class: one-step transients or "
                         "sticky permanent faults that corrupt every "
                         "matching GEMM output until cleared")
    ap.add_argument("--fault-duration", type=int, default=8,
                    help="steps a sticky permanent fault persists")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultModel RNG seed (same seed -> identical "
                         "injection schedule and classification)")
    ap.add_argument("--fault-magnitude", type=float, default=1e4,
                    help="injected value delta (0 = random exponent-bit "
                         "flips in the target dtype instead)")
    ap.add_argument("--adaptive", action="store_true",
                    help="wrap the base policy in ErrorAdaptivePolicy: "
                         "escalate to global protection when observed "
                         "detection/hard-fault rates cross thresholds, "
                         "de-escalate with hysteresis when quiet")
    ap.add_argument("--escalate-threshold", type=float, default=0.05,
                    help="windowed/EWMA detections-per-step rate that "
                         "triggers escalation (--adaptive)")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="clean recomputes after an ABFT detection")
    ap.add_argument("--raise-on-hard-fault", action="store_true",
                    help="crash instead of evicting on persistent faults")
    ap.add_argument("--cache", choices=["dense", "paged"], default="dense",
                    help="KV-cache layout (paged: block pool + tables)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged cache block size (tokens)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size (default: dense-equivalent)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted prefix sharing + copy-on-write "
                         "(paged cache, attention-only models)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="tensor-parallel width: shard params + paged KV "
                         "over a (data=1, model=N) device mesh and "
                         "compile the protection plan from the "
                         "POST-sharding per-device GEMM shapes (use "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=K to simulate devices on CPU)")
    ap.add_argument("--admit-lookahead", type=int, default=8,
                    help="bounded admission lookahead past a deferred "
                         "head request (HOL-blocking fix)")
    ap.add_argument("--chunk-tokens", type=_chunk_tokens, default=None,
                    help="chunked-prefill step token budget: decode "
                         "tokens pack first, the remainder is filled "
                         "with prompt chunks, so admission never stalls "
                         "decode (attention-only models).  'auto' picks "
                         "the smallest budget whose mixed-step intensity "
                         "clears the device CMR (roofline autotuning) "
                         "and re-tunes as occupancy drifts")
    ap.add_argument("--spec-decode", default=None,
                    choices=["ngram", "self-draft"],
                    help="speculative decoding proposer: 'ngram' "
                         "(prompt-lookup, zero model cost) or "
                         "'self-draft' (truncated-depth greedy draft "
                         "from the same weights).  Drafts run "
                         "unprotected; the K+1-token verify step goes "
                         "through the ABFT-checked path and greedy "
                         "streams stay byte-identical to the unsped "
                         "engine")
    ap.add_argument("--draft-len", type=_draft_len, default="auto",
                    help="draft tokens per verify step: an int K or "
                         "'auto' (largest K whose modeled per-emitted-"
                         "token time beats plain decode on the roofline;"
                         " re-tuned as occupancy drifts, shrunk by the "
                         "adaptive policy under escalation)")
    ap.add_argument("--draft-model", default=None, metavar="UNITS@WINDOW",
                    help="self-draft truncation spec 'units@window' "
                         "(e.g. '2@16'): how many scan units of the "
                         "serving weights the draft forward keeps and "
                         "how much trailing context it sees (only with "
                         "--spec-decode self-draft)")
    ap.add_argument("--plan-out", default=None,
                    help="dump the engine's compiled ProtectionPlan "
                         "(per-layer selections + step fast path) as a "
                         "JSON deployment artifact")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples per slot")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry metrics snapshot "
                         "(registry + fault-rate monitor + final engine "
                         "stats) as a JSON artifact")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "serving run on the tracer's own clock (host-"
                         "phase spans serve.step, serve.schedule, "
                         "serve.decode.{prepare,dispatch,wait,commit}, "
                         "serve.retry, ..., the same spans a "
                         "jax.profiler trace holds; instants: scheme "
                         "flips, evictions, fault detections)")
    ap.add_argument("--log-events", action="store_true",
                    help="stream every trace event as a JSON line to "
                         "stderr (structured event log)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.scale == "smoke":
        cfg = scaled_down(cfg)
    model = build_model(cfg)
    dtype = serving_dtype()
    params = model.init_params(jax.random.PRNGKey(0), dtype=dtype)
    abft = abft_config(args.abft, adaptive=args.adaptive,
                       escalate_threshold=args.escalate_threshold)
    fault_model = None
    if args.fault_rate > 0:
        fault_model = FaultModel(
            transient_rate=(args.fault_rate
                            if args.fault_kind == "transient" else 0.0),
            permanent_rate=(args.fault_rate
                            if args.fault_kind == "permanent" else 0.0),
            permanent_duration=args.fault_duration,
            seed=args.fault_seed, layers=cfg.n_layers,
            dtype=dtype,
            magnitude=args.fault_magnitude or None)
    policy = RecoveryPolicy(
        max_retries=args.max_retries,
        evict_on_hard_fault=not args.raise_on_hard_fault)
    draft_units, draft_window = 1, 8
    if args.draft_model:
        if args.spec_decode != "self-draft":
            ap.error("--draft-model requires --spec-decode self-draft")
        u, _, w = args.draft_model.partition("@")
        draft_units, draft_window = int(u), int(w or 8)
    telemetry = None
    if args.metrics_out or args.trace_out or args.log_events:
        sink = None
        if args.log_events:
            def sink(ev):
                print(json.dumps(ev), file=sys.stderr)
        telemetry = EngineTelemetry(
            trace=bool(args.trace_out or args.log_events),
            trace_sink=sink)
    engine = ServeEngine(model, params, slots=args.slots,
                         max_len=args.max_len, abft=abft,
                         dtype=dtype, policy=policy, mesh=args.mesh,
                         cache_kind=args.cache, block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         prefix_sharing=args.prefix_sharing,
                         admit_lookahead=args.admit_lookahead,
                         chunk_tokens=args.chunk_tokens,
                         temperature=args.temperature, top_k=args.top_k,
                         seed=args.seed, telemetry=telemetry,
                         fault_model=fault_model,
                         spec_decode=(args.spec_decode.replace("-", "_")
                                      if args.spec_decode else None),
                         draft_len=(args.draft_len
                                    if args.spec_decode else None),
                         draft_units=draft_units, draft_window=draft_window)
    heartbeats = None
    if engine.mesh is not None:
        # liveness surface for the sharded fleet: one worker per mesh
        # device, exported as worker_alive / staleness gauges on the
        # telemetry registry (runtime/heartbeat.py) — a stalled shard
        # shows up in the same metrics artifact as the engine counters
        heartbeats = HeartbeatMonitor(
            [str(d) for d in engine.mesh.devices.flat],
            registry=telemetry.registry if telemetry is not None
            else None)
    if args.plan_out:
        with open(args.plan_out, "w") as fh:
            fh.write(engine.plan.to_json())
        print(f"wrote protection plan -> {args.plan_out}")
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=rng.integers(4, 12)).astype(
                    np.int32),
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    fault_at = None
    if args.inject_faults:
        fault_at = (INJECT_STEP, injected_fault())
    # monotonic clock everywhere latency is derived: wall-clock
    # adjustments must never produce negative TTFT/ITL
    t0 = time.perf_counter()
    results = engine.run(reqs, fault_at=fault_at)
    dt = time.perf_counter() - t0
    if heartbeats is not None:
        # the in-process shards all progressed iff run() returned: beat
        # every worker once, then publish staleness as of completion
        for w in list(heartbeats.workers):
            heartbeats.beat(w)
        assert not heartbeats.check()
    if telemetry is not None:
        # TTFT/ITL histograms: the driver owns arrival time, so the
        # per-token engine stamps become latency observations here
        for r in reqs:
            if r.times:
                telemetry.observe_ttft(r.times[0] - t0)
            for a, b in zip(r.times, r.times[1:]):
                telemetry.observe_itl(b - a)
    print(json.dumps({
        "requests": len(results),
        "tokens": engine.stats.tokens,
        "tokens_per_s": engine.stats.tokens / dt,
        "faults_detected": engine.stats.faults_detected,
        "retries": engine.stats.retries,
        "hard_faults": engine.stats.hard_faults,
        "evictions": engine.stats.evictions,
        "rejections": engine.stats.rejections,
        "prefix_hit_rate": engine.stats.prefix_hit_rate,
        "cow_copies": engine.stats.cow_copies,
        "prefill_chunks": engine.stats.prefill_chunks,
        "mixed_steps": engine.stats.mixed_steps,
        "decode_only_steps": engine.stats.decode_only_steps,
        "campaign": ({
            "faults_injected": engine.stats.faults_injected,
            "faults_corrected": engine.stats.faults_corrected,
            "faults_uncorrected": engine.stats.faults_uncorrected,
            "sdc_faults": engine.stats.sdc_faults,
            "masked_faults": engine.stats.masked_faults,
            "schedule": fault_model.schedule,
        } if fault_model is not None else None),
        "protection_level": engine.protection_level,
        "protection_escalations": engine.stats.protection_escalations,
        "protection_deescalations":
            engine.stats.protection_deescalations,
        "chunk_tokens": engine.chunk_tokens,
        "chunk_budget_retunes": engine.stats.chunk_budget_retunes,
        "spec_decode": ({
            "proposer": engine.spec.name,
            "draft_len": engine.draft_len,
            "draft_proposed": engine.stats.draft_proposed,
            "draft_accepted": engine.stats.draft_accepted,
            "accept_rate": (engine.stats.draft_accepted
                            / engine.stats.draft_proposed
                            if engine.stats.draft_proposed else None),
            "verify_retries": engine.stats.verify_retries,
        } if engine.spec is not None else None),
        "model_parallel": engine.model_parallel,
        "shard_plan": ([{"layer": r["layer"], "scheme": r["scheme"],
                         "ai": r["ai"], "bound": r["bound"]}
                        for r in engine.plan.report_rows()]
                       if engine.mesh is not None else None),
        "errors": {r.uid: r.error for r in reqs if r.error},
        "cache": engine.cache_stats(),
        "telemetry": (telemetry.faults.snapshot()
                      if telemetry is not None else None),
    }))
    if args.metrics_out:
        stats = engine.stats
        artifact = telemetry.snapshot()
        artifact["engine_stats"] = {
            k: getattr(stats, a) for k, a in ENGINE_COUNTERS.items()}
        artifact["counters_match_stats"] = telemetry.counters_match(stats)
        with open(args.metrics_out, "w") as fh:
            json.dump(artifact, fh, indent=2)
        print(f"wrote metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        telemetry.tracer.write(args.trace_out)
        print(f"wrote trace ({len(telemetry.tracer.events)} events) -> "
              f"{args.trace_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
