"""Serving throughput sweep: tokens/s AND latency tails under continuous
batching, over slots x prompt-length mix x ABFT scheme x cache kind
(ROADMAP open item, paper §6 deployment scenario).

For each cell the engine serves a fixed request set end to end and we
report wall-clock tokens/s, p50/p95/p99 TTFT and inter-token-latency
percentiles (every generated token is wall-clock stamped by the engine),
plus ``cache_stats()`` — the paged cells size their pool to the traffic's
peak *working set* (not slots × max_len), so a skewed prompt mix shows
the paged cache allocating a fraction of the dense bytes while producing
the identical greedy token streams.

The ``templated`` mix models system-prompt traffic: every request opens
with the same template and differs only in a short tail.  Its cells add
a ``paged_shared`` engine (refcounted prefix sharing + copy-on-write):
streams must stay byte-identical to dense AND unshared-paged while the
per-step mean ``blocks_used`` drops ≥2x (the shared template is resident
ONCE, chained through overlapping sharers, instead of once per slot).

The ``long_prompt`` mix exposes the admission stall: mostly-short
traffic with rare near-max-length prompts.  Unchunked engines prefill a
long prompt in ONE model call on the decode path, so every resident
stream's inter-token gap spikes — visible as the p99 ITL.  The
``paged_chunked`` cells (chunked-prefill scheduler, ``chunk_tokens``
step budget) bound the co-scheduled prefill work per step; the
acceptance metric is ``chunked_itl_p99_frac`` (chunked p99 ITL over the
admit-time-prefill baseline) at equal throughput with byte-identical
streams.  Chunked cells also report the per-step intensity-guided
``selection`` summary (mixed vs decode-only step compositions and the
schemes the selector picked for them).

The ``chunked_auto`` cell (long_prompt mix, intensity_guided scheme)
exercises ``ServeEngine(chunk_tokens="auto")``: the step budget comes
from ``ProtectionPlan.tune_chunk_budget`` — the smallest budget whose
mixed-step arithmetic intensity clears the device CMR — instead of a
flag.  Its acceptance keys: ``auto_matches_dense`` (byte-identical
streams), ``auto_clears_cmr`` (the tuned budget's intensity vs the CMR),
and ``auto_tput_frac`` (auto throughput over the best FIXED budget from
the half/default/double ``fixed_budget_sweep``).

Every cell reports the fixed occupancy accounting — ``utilization``
against allocated tokens, ``fragmentation``, ``blocks_shared``,
``prefix_hit_rate`` — plus the ``rejections`` / ``evictions`` split.

The ``spec_decode`` sweep serves periodic prompts (the prompt-lookup
best case) through speculative engines: draft length K x proposer x
ABFT scheme, each against an unsped baseline of the SAME engine
geometry.  Acceptance keys per row: ``spec_matches_dense`` (greedy
streams byte-identical to the unsped run — speculation is an execution
strategy, not an approximation), ``accept_rate``, and
``spec_tput_frac``.  The intensity-guided rows run under
``AUTO_TUNE_HW``, crafted so plain decode (slots tokens) sits below the
CMR while a full K=4 verify window (slots x 5 tokens) clears it: the
sweep's ``verify_schemes`` show the per-step selector flipping
``block_1s`` -> ``global`` as K grows, with the matching
``scheme_flips`` counts.  The ``tuned`` row runs ``draft_len="auto"``
(``ProtectionPlan.tune_draft_len`` picks K from the roofline + the
acceptance-rate prior); its gate is ``tuned_beats_fixed_median`` —
tuned-K throughput at least the median of the fixed-K rows under the
same scheme.

``--mesh 1,2,4`` adds a sharded sweep: bf16 params + paged KV sharded
over a (data=1, model=N) device mesh per width, each engine compiling
its protection plan from the POST-sharding per-device GEMM shapes
(``SHARD_SWEEP_HW`` is crafted so the selector lands on different
schemes per width).  Each row reports tokens/s, the per-shard scheme
table, and ``matches_mesh1`` — greedy streams must stay byte-identical
to the width-1 baseline.

  PYTHONPATH=src python benchmarks/serve_throughput.py \
      [--quick] [--out results.json] [--slots 2,4] [--new-tokens 8] \
      [--mixes uniform_short,long_prompt] [--chunk-tokens 16]

Wall-clock numbers are CPU-measured (this container); they order schemes
by redundant-work cost, not by TPU speed — see benchmarks/common.py.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, FixedPolicy, Scheme, compute_bound_ai
from repro.core.hardware import HardwareSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.obs import EngineTelemetry
from repro.serve.engine import EngineStats, Request, ServeEngine
from repro.serve.paged_cache import blocks_for

SCHEMES = {
    # none: protection off; traditional: one global checksum for every
    # layer (Hari et al.); guided: the paper's intensity-guided selector
    "none": ABFTConfig.off(),
    "traditional": ABFTConfig.from_policy(
        FixedPolicy(Scheme.GLOBAL), use_pallas=False),
    "intensity_guided": ABFTConfig(scheme=Scheme.AUTO, use_pallas=False),
}

# Hardware for the --mesh sweep's per-shard plans: CMR=24 sits between
# the smoke model's full-width mlp/lm_head intensities (25.6/28.4) and
# every 4-way shard's (<=21.3), and the slow-VPU/cheap-fixed-op balance
# makes global ABFT's dispatch cost amortize only over the full-width
# GEMMs — so the width sweep shows the selector flipping scheme per
# shard (tests/test_sharded_engine.py asserts the same divergence)
SHARD_SWEEP_HW = HardwareSpec(
    name="shard-flip", peak_flops=2.4e13, vpu_flops=1e11, hbm_bw=1e12,
    ici_bw=1e11, hbm_bytes=1 << 34, vmem_bytes=1 << 24,
    fixed_op_overhead_s=1e-7)

# Hardware for the chunked_auto cell's budget autotuning AND the
# spec_decode sweep: a CMR the benchmark's scaled step geometry (k=64,
# n=128, f32) can actually clear, so tune_chunk_budget has a real
# roofline crossing to find instead of saturating at the max_len cap
# (the real v5e CMR of ~241 is unreachable for a 64-wide d_model —
# crafted specs are how the selection tests exercise the crossover
# too).  Same ratios as the FLIP_HW test spec.  The step-composition
# crossover sits at 18 tokens: plain decode at 4 slots (4 tokens) is
# memory-bound -> block_1s, a K=4 verify window (4 x 5 = 20 tokens)
# clears the CMR -> global — the spec sweep's scheme-flip evidence.
AUTO_TUNE_HW = HardwareSpec(
    name="bench-flip", peak_flops=1e10, vpu_flops=2.6e8, hbm_bw=1e9,
    ici_bw=1e9, hbm_bytes=1 << 30, vmem_bytes=1 << 20,
    fixed_op_overhead_s=1e-6)

MIXES = {
    # (length, weight) pairs; lengths are fractions of max_len
    "uniform_short": [(0.15, 1.0)],
    "skewed": [(0.08, 3.0), (0.75, 1.0)],   # mostly short + one long tail
    # mostly short with periodic near-max prompts arriving mid-flight:
    # the admission-stall / chunked-prefill showcase (the long prefill
    # is what spikes resident streams' p99 ITL)
    "long_prompt": "long_prompt",
    # system-prompt traffic: shared template + short unique tail (the
    # prefix-sharing best case; worst case for unshared paging)
    "templated": "templated",
}

# template length as a fraction of max_len; 0.75 keeps the default
# geometry block-aligned (48 tokens = 3 x 16-token blocks), so sharers
# alias whole template blocks and own only their tail/decode block
TEMPLATE_FRAC = 0.75


def _requests(mix, n: int, max_len: int, new_tokens: int) -> tuple:
    rng = np.random.default_rng(0)
    if mix == "long_prompt":
        # deterministic arrival pattern: short prompts with LONG decode
        # budgets get resident first, then every 4th request is a
        # near-max prompt whose admission (or chunk stream) lands while
        # they are still decoding — the staggered budgets guarantee the
        # overlap that makes the admission stall visible in their
        # inter-token gaps
        short = max(2, int(0.04 * max_len))
        long = max(short + 1, int(0.88 * max_len))
        reqs, lens = [], []
        for i in range(n):
            if i % 4 == 2:
                L, budget = long, new_tokens
            else:
                L, budget = short, 3 * new_tokens + i % 3
            reqs.append(Request(
                uid=i, prompt=(1 + np.arange(L, dtype=np.int32) % 250),
                max_new_tokens=budget))
            lens.append(L)
        return reqs, lens
    if mix == "templated":
        # one fixed template, per-request tails of 1-4 tokens, and
        # staggered decode budgets — overlap is what lets later requests
        # share the template blocks a live sharer keeps resident
        tpl_len = max(2, int(TEMPLATE_FRAC * max_len))
        template = 1 + np.arange(tpl_len, dtype=np.int32) % 250
        reqs, lens = [], []
        for i in range(n):
            tail = 1 + (50 + 13 * i + np.arange(1 + i % 4,
                                                dtype=np.int32)) % 250
            prompt = np.concatenate([template, tail])
            budget = max(2, new_tokens - 2 + (i * 3) % 5)
            reqs.append(Request(uid=i, prompt=prompt,
                                max_new_tokens=budget))
            lens.append(len(prompt))
        return reqs, lens
    fracs, weights = zip(*mix)
    w = np.asarray(weights) / sum(weights)
    lens = [int(max(2, rng.choice(fracs, p=w) * max_len)) for _ in range(n)]
    return [
        Request(uid=i, prompt=(1 + np.arange(L, dtype=np.int32) % 250),
                max_new_tokens=new_tokens)
        for i, L in enumerate(lens)
    ], lens


def _spec_requests(n: int, max_len: int, new_tokens: int) -> list:
    """Periodic prompts for the spec_decode sweep: the trailing n-gram
    recurs throughout the prompt, so the prompt-lookup proposer finds
    continuations and acceptance stays high — the traffic regime
    speculative decoding is built for (greedy equality must hold for
    ANY acceptance rate; the tests cover the adversarial end)."""
    reqs = []
    for i in range(n):
        pat = 3 + np.arange(4 + i % 2, dtype=np.int32)
        L = max(8, int(0.4 * max_len)) + i % 3
        reqs.append(Request(
            uid=i, prompt=np.tile(pat, max_len)[:L],
            max_new_tokens=new_tokens))
    return reqs


def _pool_blocks(lens, slots, new_tokens, block_size) -> int:
    """Blocks covering the peak per-slot working set of this traffic:
    the ``slots`` largest requests resident at once, each grown to
    prompt + decode budget."""
    need = sorted((blocks_for(L + new_tokens, block_size) for L in lens),
                  reverse=True)
    return max(1, sum(need[:slots]))


def _percentiles_ms(samples) -> dict:
    """p50/p95/p99 of a latency sample list, in milliseconds."""
    if not samples:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    xs = np.asarray(samples, np.float64) * 1e3
    return {"p50": float(np.percentile(xs, 50)),
            "p95": float(np.percentile(xs, 95)),
            "p99": float(np.percentile(xs, 99))}


def _latency_stats(reqs, t0: float) -> dict:
    """TTFT (first stamped token minus batch-arrival t0) and pooled
    inter-token gaps, from the engine's per-token wall-clock stamps."""
    ttft = [r.times[0] - t0 for r in reqs if r.times]
    itl = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])]
    return {"ttft_ms": _percentiles_ms(ttft), "itl_ms": _percentiles_ms(itl)}


def _selection_summary(stats: EngineStats) -> dict:
    """Condense the per-step (intensity, scheme) trace: how often the
    step composition was mixed vs decode-only, the mean intensity of
    each, and which schemes the selector picked."""
    tr = stats.selection_trace
    mixed = [e["intensity"] for e in tr if e["decode"] and e["prefill"]]
    dec = [e["intensity"] for e in tr if e["decode"] and not e["prefill"]]
    return {
        "mixed_steps": stats.mixed_steps,
        "decode_only_steps": stats.decode_only_steps,
        "prefill_only_steps": stats.prefill_only_steps,
        "intensity_mixed_mean": float(np.mean(mixed)) if mixed else 0.0,
        "intensity_decode_mean": float(np.mean(dec)) if dec else 0.0,
        "schemes": dict(collections.Counter(e["scheme"] for e in tr)),
    }


def run_cell(model, params, reqs, *, slots, max_len, abft, cache_kind,
             num_blocks=None, block_size=16,
             prefix_sharing=False, chunk_tokens=None, mesh=None,
             spec_decode=None, draft_len=None,
             dtype=jnp.float32,
             telemetry: EngineTelemetry | None = None) -> dict:
    eng = ServeEngine(
        model, params, slots=slots, max_len=max_len, abft=abft,
        dtype=dtype, cache_kind=cache_kind, block_size=block_size,
        num_blocks=num_blocks, prefix_sharing=prefix_sharing,
        chunk_tokens=chunk_tokens, mesh=mesh,
        spec_decode=spec_decode, draft_len=draft_len)
    # warm-up pass: serve a throwaway copy of the same traffic so jit
    # compilation (which dominates cold wall time on CPU) is excluded
    # from the reported tokens/s; shapes repeat, so the timed run below
    # hits the compile cache
    eng.run([Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens) for r in reqs])
    if eng.pool is not None:
        eng.pool.reset()            # warm-up must not seed the shared run
    if eng.index is not None:
        from repro.serve.paged_cache import PrefixIndex

        eng.index = PrefixIndex(block_size)
    eng.stats = EngineStats()
    if telemetry is not None:
        # attach AFTER the warm-up + stats reset: the mirrored counters
        # are monotonic and must start from the fresh EngineStats (the
        # timed run is also the only one worth exporting)
        eng.attach_telemetry(telemetry)
    t0 = time.perf_counter()
    eng.run([r for r in reqs])
    dt = time.perf_counter() - t0
    if telemetry is not None:
        for r in reqs:
            if r.times:
                telemetry.observe_ttft(r.times[0] - t0)
            for a, b in zip(r.times, r.times[1:]):
                telemetry.observe_itl(b - a)
    stats = eng.cache_stats()
    cell = {
        "tokens": eng.stats.tokens,
        "tokens_per_s": eng.stats.tokens / dt,
        "wall_s": dt,
        "errors": sum(1 for r in reqs if r.error),
        "rejections": eng.stats.rejections,
        "evictions": eng.stats.evictions,
        "cache_bytes": stats["bytes_total"],
        "tokens_capacity": stats["tokens_capacity"],
        "utilization": stats["utilization"],
        "fragmentation": stats["fragmentation"],
        "blocks_shared": stats["blocks_shared"],
        "prefix_hit_rate": stats["prefix_hit_rate"],
        "blocks_used_mean": eng.stats.blocks_used_mean,
        "blocks_used_median": eng.stats.blocks_used_median,
        "blocks_used_peak": eng.stats.blocks_used_peak,
        "blocks_shared_peak": eng.stats.blocks_shared_peak,
        "cow_copies": eng.stats.cow_copies,
        "prefill_chunks": eng.stats.prefill_chunks,
        "selection": _selection_summary(eng.stats),
        "streams": {r.uid: r.generated for r in reqs},
    }
    if mesh is not None:
        # the per-shard protection plan: compiled from POST-sharding
        # per-device GEMM shapes, so a width sweep shows the
        # intensity-guided selection re-deciding as TP narrows the GEMMs
        cell["model_parallel"] = eng.model_parallel
        cell["shard_plan"] = [
            {"layer": r["layer"], "scheme": r["scheme"],
             "ai": r["ai"], "bound": r["bound"]}
            for r in eng.plan.report_rows()]
    if chunk_tokens is not None:
        # the EFFECTIVE budget (chunk_tokens="auto" resolves it via the
        # plan's roofline autotuner and may re-tune mid-run) plus the
        # intensity evidence behind it and the plan's modeled step
        # throughput (wall clock on this CPU container is dispatch-
        # dominated; the model is the device-relevant ordering)
        cell["chunk_budget"] = eng.chunk_tokens
        cell["budget_retunes"] = eng.stats.chunk_budget_retunes
        cell["mixed_step_intensity"] = eng.plan.step_intensity(
            eng.chunk_tokens)
        cell["cmr"] = eng.plan.hardware.cmr
        cell["modeled_step_tput"] = (
            eng.chunk_tokens / eng.plan.modeled_step_time(eng.chunk_tokens))
    if spec_decode is not None:
        # the speculative accounting the acceptance criteria key on:
        # draft economics + which schemes the per-step selector picked
        # for the K-scaled verify windows
        prop = eng.stats.draft_proposed
        cell["spec"] = {
            "proposer": eng.spec.name,
            "draft_len": eng.draft_len,
            "draft_proposed": prop,
            "draft_accepted": eng.stats.draft_accepted,
            "accept_rate": eng.stats.draft_accepted / max(prop, 1),
            "verify_retries": eng.stats.verify_retries,
            "scheme_flips": eng.stats.scheme_flips,
            # schemes of the decode-composition steps only (the verify
            # windows); prefill steps are compute-bound on any hardware
            # and would mask the K-driven crossover
            "schemes": dict(collections.Counter(
                e["scheme"] for e in eng.stats.selection_trace
                if e["decode"] and not e["prefill"])),
        }
    cell.update(_latency_stats(reqs, t0))
    if telemetry is not None:
        cell["telemetry"] = dict(
            telemetry.snapshot(),
            counters_match_stats=telemetry.counters_match(eng.stats),
            trace_events=list(telemetry.tracer.events))
    return cell


def _spec_sweep(model, params, args) -> dict:
    """The speculative-decoding sweep: draft length K x proposer x ABFT
    scheme over periodic-prompt traffic, each row judged against an
    unsped baseline of the same engine geometry.  Runs at 4 slots
    regardless of ``--slots``: the AUTO_TUNE_HW crossover sits at 18
    step tokens, so 4-slot plain decode (4 tokens) stays memory-bound
    while a full K=4 verify window (20 tokens) clears the CMR — the
    scheme-flip evidence the sweep exists to produce."""
    slots = 4
    ks = [1, 4] if args.quick else [1, 2, 4]
    proposers = ["ngram"] if args.quick else ["ngram", "self_draft"]
    # decode budgets long enough that the steady state (full-K windows
    # once the proposer locks on) dominates the first-step ramp-in
    new_toks = max(args.new_tokens, 16)
    reqs_proto = _spec_requests(args.requests, args.max_len, new_toks)
    lens = [len(r.prompt) for r in reqs_proto]
    # a verify step grows each slot's KV by up to K+1 tokens before the
    # acceptance cursor settles; size the pool with tuned-K headroom
    nb = _pool_blocks(lens, slots, new_toks + 9, args.block_size)
    schemes = {
        "none": ABFTConfig.off(),
        "intensity_guided": ABFTConfig(
            scheme=Scheme.AUTO, use_pallas=False, hardware=AUTO_TUNE_HW),
    }

    def cell(**kw):
        reqs = [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens)
                for r in reqs_proto]
        return run_cell(model, params, reqs, slots=slots,
                        max_len=args.max_len, cache_kind="paged",
                        num_blocks=nb, block_size=args.block_size, **kw)

    rows = []
    base_tput, base_streams, decode_scheme = {}, {}, None
    for scheme_name, abft in schemes.items():
        base = cell(abft=abft)
        base_streams[scheme_name] = base.pop("streams")
        base_tput[scheme_name] = base["tokens_per_s"]
        if scheme_name == "intensity_guided":
            sel = base["selection"]["schemes"]
            decode_scheme = max(sel, key=sel.get) if sel else None
        for prop in proposers:
            for k in ks:
                c = cell(abft=abft, spec_decode=prop, draft_len=k)
                streams = c.pop("streams")
                row = dict(
                    c, scheme=scheme_name, proposer=prop, draft_len=k,
                    spec_matches_dense=(
                        streams == base_streams[scheme_name]),
                    spec_tput_frac=(c["tokens_per_s"]
                                    / max(base_tput[scheme_name], 1e-9)))
                rows.append(row)
                print(f"spec  scheme={scheme_name:16s} "
                      f"proposer={prop:10s} K={k} "
                      f"accept={row['spec']['accept_rate']:.2f} "
                      f"tput={row['spec_tput_frac']:.2f}x "
                      f"match={row['spec_matches_dense']} "
                      f"schemes={row['spec']['schemes']}")

    # tuned row: draft_len="auto" resolves K via the plan's roofline +
    # acceptance-rate prior (ProtectionPlan.tune_draft_len); acceptance
    # is throughput at least the median of the fixed-K rows under the
    # same scheme and proposer
    tuned_c = cell(abft=schemes["intensity_guided"], spec_decode="ngram",
                   draft_len="auto")
    t_streams = tuned_c.pop("streams")
    tuned = dict(
        tuned_c, scheme="intensity_guided", proposer="ngram",
        draft_len="auto",
        tuned_draft_len=tuned_c["spec"]["draft_len"],
        spec_matches_dense=(
            t_streams == base_streams["intensity_guided"]),
        spec_tput_frac=(tuned_c["tokens_per_s"]
                        / max(base_tput["intensity_guided"], 1e-9)))
    fixed = [r["tokens_per_s"] for r in rows
             if r["scheme"] == "intensity_guided"
             and r["proposer"] == "ngram"]
    median = float(np.median(fixed)) if fixed else 0.0
    verify_schemes = {
        str(r["draft_len"]): r["spec"]["schemes"]
        for r in rows if r["scheme"] == "intensity_guided"
        and r["proposer"] == "ngram"}
    # the scheme-flip evidence: some K's verify windows cross the CMR
    # and land on a scheme plain decode never selects
    flipped = decode_scheme is not None and any(
        s != decode_scheme
        for v in verify_schemes.values() for s in v)
    out = {
        "hardware": AUTO_TUNE_HW.name, "slots": slots,
        "draft_lens": ks, "proposers": proposers,
        "baseline_tokens_per_s": base_tput,
        "rows": rows, "tuned": tuned,
        "tuned_draft_len": tuned["tuned_draft_len"],
        "fixed_tput_median": median,
        "tuned_beats_fixed_median": tuned["tokens_per_s"] >= median,
        "decode_scheme": decode_scheme,
        "verify_schemes": verify_schemes,
        "scheme_flipped": flipped,
    }
    print(f"spec  tuned_draft_len={out['tuned_draft_len']} "
          f"tuned_tput={tuned['tokens_per_s']:.1f} tok/s "
          f"(fixed median {median:.1f}) "
          f"beats_median={out['tuned_beats_fixed_median']} "
          f"decode_scheme={decode_scheme} "
          f"verify_schemes={verify_schemes} flip={flipped}")
    return out


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--slots", default="2,4")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=6)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="step token budget of the paged_chunked cells "
                         "(0 = auto: max(16, mix max_len // 4))")
    ap.add_argument("--long-max-len", type=int, default=768,
                    help="cache depth of the long_prompt mix (the "
                         "admission stall needs prompts long enough that "
                         "prefill cost is token-dominated, not "
                         "dispatch-dominated)")
    ap.add_argument("--mixes", default=None,
                    help="comma-separated subset of mixes to run "
                         f"(default all: {','.join(MIXES)})")
    ap.add_argument("--mesh", default=None,
                    help="comma-separated tensor-parallel widths (e.g. "
                         "'1,2,4'): adds a sharded sweep — params + paged "
                         "KV sharded over a (data=1, model=N) mesh, bf16, "
                         "per-shard intensity-guided plans — reporting "
                         "tokens/s, the per-shard scheme table, and "
                         "stream equality vs the width-1 baseline (widths "
                         "beyond the visible device count are skipped; "
                         "use XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 on CPU)")
    ap.add_argument("--quick", action="store_true",
                    help="one slot count, two schemes")
    ap.add_argument("--out", default=None, help="write JSON here (else stdout)")
    ap.add_argument("--telemetry-out", default=None,
                    help="write a per-cell telemetry artifact: metrics "
                         "snapshot, fault-rate surface, and a bounded "
                         "span trace per engine cell (schema-gated in "
                         "CI by benchmarks/check_telemetry_schema.py)")
    args = ap.parse_args(argv)

    cfg = scaled_down(get_config(args.arch), n_layers=args.n_layers)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)

    slot_counts = [int(s) for s in str(args.slots).split(",")]
    schemes = dict(SCHEMES)
    if args.quick:
        slot_counts = slot_counts[:1]
        schemes = {k: schemes[k] for k in ("none", "intensity_guided")}
    mixes = dict(MIXES)
    if args.mixes:
        names = [m.strip() for m in str(args.mixes).split(",") if m.strip()]
        unknown = [m for m in names if m not in MIXES]
        if unknown:
            raise SystemExit(f"unknown mixes {unknown}; known: {list(MIXES)}")
        mixes = {m: MIXES[m] for m in names}

    share_ok = model.supports_prefix_sharing
    chunk_ok = model.supports_chunked_prefill
    cells = []
    telemetry_cells = []
    for slots in slot_counts:
        for mix_name, mix in mixes.items():
            n_reqs = args.requests
            if mix_name == "templated":
                # enough waves that the steady state (one resident
                # template chained through overlapping sharers) dominates
                # the cold-start wave of unshared copies
                n_reqs = max(args.requests, 6 * slots)
            mix_max_len = (max(args.max_len, args.long_max_len)
                           if mix_name == "long_prompt" else args.max_len)
            chunk_tokens = (args.chunk_tokens
                            or max(16, mix_max_len // 4))
            reqs_proto, lens = _requests(
                mix, n_reqs, mix_max_len, args.new_tokens)
            peak_new = max(r.max_new_tokens for r in reqs_proto)
            nb = _pool_blocks(lens, slots, peak_new, args.block_size)
            kinds = ["dense", "paged"]
            if share_ok:
                kinds.append("paged_shared")
            if chunk_ok:
                kinds.append("paged_chunked")
            for scheme_name, abft in schemes.items():
                row = {"slots": slots, "mix": mix_name,
                       "scheme": scheme_name,
                       "max_len": mix_max_len,
                       # the EFFECTIVE step budget the paged_chunked cell
                       # ran with (the --chunk-tokens flag may be 0=auto)
                       "chunk_tokens": chunk_tokens,
                       "prompt_lens": lens}
                streams = {}
                for kind in kinds:
                    reqs = [Request(uid=r.uid, prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens)
                            for r in reqs_proto]
                    # one fresh telemetry per cell (counters mirror ONE
                    # engine's stats); the trace is event-bounded so the
                    # artifact stays small across the whole sweep
                    tel = (EngineTelemetry(trace=True,
                                           trace_max_events=2000)
                           if args.telemetry_out else None)
                    cell = run_cell(
                        model, params, reqs, slots=slots,
                        max_len=mix_max_len, abft=abft,
                        cache_kind="dense" if kind == "dense" else "paged",
                        block_size=args.block_size,
                        num_blocks=None if kind == "dense" else nb,
                        prefix_sharing=(kind == "paged_shared"),
                        chunk_tokens=(chunk_tokens
                                      if kind == "paged_chunked" else None),
                        telemetry=tel)
                    if tel is not None:
                        telemetry_cells.append(dict(
                            {"slots": slots, "mix": mix_name,
                             "scheme": scheme_name, "kind": kind},
                            **cell.pop("telemetry")))
                    streams[kind] = cell.pop("streams")
                    row[kind] = cell
                row["paged_matches_dense"] = (
                    streams["dense"] == streams["paged"])
                row["paged_bytes_frac"] = (
                    row["paged"]["cache_bytes"]
                    / max(row["dense"]["cache_bytes"], 1))
                shared_note = ""
                if share_ok:
                    row["shared_matches_dense"] = (
                        streams["dense"] == streams["paged_shared"])
                    # the acceptance metric: steady-state resident blocks
                    # at equal throughput, shared vs unshared paging (the
                    # median discounts the cold-start wave, which by
                    # construction cannot share — nothing is cached yet)
                    row["shared_blocks_frac"] = (
                        row["paged_shared"]["blocks_used_median"]
                        / max(row["paged"]["blocks_used_median"], 1e-9))
                    shared_note = (
                        f" shared_blocks={row['shared_blocks_frac']:.2f}x "
                        f"hit={row['paged_shared']['prefix_hit_rate']:.2f} "
                        f"match={row['shared_matches_dense']}")
                auto_note = ""
                if chunk_ok and mix_name == "long_prompt" and \
                        scheme_name == "intensity_guided":
                    # chunked_auto: the budget comes from the plan's
                    # roofline autotuner (smallest mixed-step budget
                    # clearing the AUTO_TUNE_HW CMR with the modeled
                    # 10% throughput margin) instead of a flag.
                    # Acceptance: streams stay byte-identical to dense,
                    # the tuned budget clears the CMR, and modeled
                    # throughput lands within 10% of the best FIXED
                    # budget from the half/double bracketing sweep (run
                    # under the SAME hardware spec, so the comparison is
                    # budget-vs-budget, not scheme-vs-scheme).
                    auto_abft = dataclasses.replace(
                        abft, hardware=AUTO_TUNE_HW)
                    auto_cell = run_cell(
                        model, params,
                        [Request(uid=r.uid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)
                         for r in reqs_proto],
                        slots=slots, max_len=mix_max_len,
                        abft=auto_abft,
                        cache_kind="paged", block_size=args.block_size,
                        num_blocks=nb, chunk_tokens="auto")
                    streams["chunked_auto"] = auto_cell.pop("streams")
                    row["chunked_auto"] = auto_cell
                    auto_b = auto_cell["chunk_budget"]
                    row["auto_budget"] = auto_b
                    row["auto_matches_dense"] = (
                        streams["dense"] == streams["chunked_auto"])
                    row["auto_clears_cmr"] = compute_bound_ai(
                        auto_cell["mixed_step_intensity"], AUTO_TUNE_HW)
                    sweep = {}
                    for b in sorted({max(8, auto_b // 2 // 8 * 8),
                                     2 * auto_b, chunk_tokens}):
                        scell = run_cell(
                            model, params,
                            [Request(uid=r.uid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens)
                             for r in reqs_proto],
                            slots=slots, max_len=mix_max_len,
                            abft=auto_abft, cache_kind="paged",
                            block_size=args.block_size,
                            num_blocks=nb, chunk_tokens=b)
                        s_streams = scell.pop("streams")
                        sweep[str(b)] = {
                            "tokens_per_s": scell["tokens_per_s"],
                            "modeled_step_tput":
                                scell["modeled_step_tput"],
                            "matches_dense":
                                s_streams == streams["dense"],
                        }
                    row["fixed_budget_sweep"] = sweep
                    row["auto_tput_frac"] = (
                        auto_cell["tokens_per_s"]
                        / max(max(v["tokens_per_s"]
                                  for v in sweep.values()), 1e-9))
                    row["auto_modeled_tput_frac"] = (
                        auto_cell["modeled_step_tput"]
                        / max(max(v["modeled_step_tput"]
                                  for v in sweep.values()), 1e-9))
                    auto_note = (
                        f" auto_budget={row['auto_budget']}"
                        f" auto_tput={row['auto_tput_frac']:.2f}x"
                        f" (modeled {row['auto_modeled_tput_frac']:.2f}x)"
                        f" clears_cmr={row['auto_clears_cmr']}")
                chunk_note = ""
                if chunk_ok:
                    # the chunked-prefill acceptance metrics: byte-equal
                    # streams, equal-throughput p99 ITL vs the admit-time
                    # -prefill paged baseline (the long_prompt mix is the
                    # cell where the stall lives)
                    row["chunked_matches_dense"] = (
                        streams["dense"] == streams["paged_chunked"])
                    row["chunked_itl_p99_frac"] = (
                        row["paged_chunked"]["itl_ms"]["p99"]
                        / max(row["paged"]["itl_ms"]["p99"], 1e-9))
                    row["chunked_tput_frac"] = (
                        row["paged_chunked"]["tokens_per_s"]
                        / max(row["paged"]["tokens_per_s"], 1e-9))
                    chunk_note = (
                        f" chunked_itl_p99={row['chunked_itl_p99_frac']:.2f}x"
                        f" match={row['chunked_matches_dense']}")
                cells.append(row)
                print(f"slots={slots} mix={mix_name:13s} "
                      f"scheme={scheme_name:16s} "
                      f"dense={row['dense']['tokens_per_s']:8.1f} tok/s "
                      f"paged={row['paged']['tokens_per_s']:8.1f} tok/s "
                      f"bytes={row['paged_bytes_frac']:.2f}x "
                      f"match={row['paged_matches_dense']}"
                      + shared_note + chunk_note + auto_note)

    # speculative decoding needs the rollback guarantees chunked prefill
    # needs too (attention-only cache writes, no SSM recurrence)
    spec_sweep = _spec_sweep(model, params, args) if chunk_ok else None

    sharded = None
    if args.mesh:
        widths = sorted({int(w) for w in str(args.mesh).split(",")})
        ndev = len(jax.devices())
        # bf16: per-device partial GEMMs accumulate in f32 and round
        # below output precision, so streams stay byte-identical across
        # widths (the equality verdict below is exact, not approximate)
        params_b = model.init_params(jax.random.PRNGKey(0),
                                     dtype=jnp.bfloat16)
        abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False,
                          hardware=SHARD_SWEEP_HW)
        reqs_proto, lens = _requests(MIXES["uniform_short"],
                                     args.requests, args.max_len,
                                     args.new_tokens)
        nb = _pool_blocks(lens, slot_counts[0], args.new_tokens,
                          args.block_size)
        rows, base_streams = [], None
        for w in widths:
            if w > ndev:
                rows.append({"mesh": w, "skipped":
                             f"needs {w} devices, have {ndev}"})
                print(f"mesh={w}: skipped ({w} > {ndev} devices)")
                continue
            cell = run_cell(
                model, params_b,
                [Request(uid=r.uid, prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens)
                 for r in reqs_proto],
                slots=slot_counts[0], max_len=args.max_len, abft=abft,
                cache_kind="paged", block_size=args.block_size,
                num_blocks=nb, mesh=w, dtype=jnp.bfloat16)
            streams = cell.pop("streams")
            if base_streams is None:
                base_streams = streams
            cell["mesh"] = w
            cell["matches_mesh1"] = streams == base_streams
            rows.append(cell)
            schemes_now = collections.Counter(
                e["scheme"] for e in cell["shard_plan"])
            print(f"mesh={w} tok/s={cell['tokens_per_s']:8.1f} "
                  f"matches_mesh1={cell['matches_mesh1']} "
                  f"shard_schemes={dict(schemes_now)}")
        # the engine rows above carry decode-shaped plans (m = slots,
        # bandwidth-bound at smoke scale); the divergence the paper's
        # selector exhibits lives at prefill-representative token counts,
        # so also compile the per-width plans at n_tokens=64 — device-
        # independent, covers skipped widths too
        divergence = {}
        for w in widths:
            p = model.protection_plan(
                hw=SHARD_SWEEP_HW, phase="serve", n_tokens=64,
                dtype_bytes=2, model_parallel=w)
            divergence[str(w)] = {
                r["layer"]: r["scheme"] for r in p.report_rows()}
        flipped = sorted(
            layer for layer in divergence[str(widths[0])]
            if len({d[layer] for d in divergence.values()}) > 1)
        print(f"per-shard plan divergence (n_tokens=64): "
              f"{flipped or 'none'}")
        sharded = {"widths": widths, "devices": ndev,
                   "hardware": SHARD_SWEEP_HW.name, "rows": rows,
                   "plan_divergence": divergence,
                   "layers_flipping_scheme": flipped}

    summary = {
        "arch": args.arch, "n_layers": args.n_layers,
        "max_len": args.max_len, "requests": args.requests,
        "new_tokens": args.new_tokens, "block_size": args.block_size,
        "chunk_tokens_flag": args.chunk_tokens,   # 0 = auto, see cells
        "mixes": list(mixes),
        "backend": jax.default_backend(),
        "cells": cells,
        "spec_decode": spec_sweep,
        "sharded": sharded,
    }
    payload = json.dumps(summary, indent=2)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(payload)
        print(f"wrote {args.out}")
    else:
        print(payload)
    if args.telemetry_out:
        with open(args.telemetry_out, "w") as fh:
            json.dump({"schema_version": 1, "cells": telemetry_cells},
                      fh, indent=2)
        print(f"wrote {args.telemetry_out} "
              f"({len(telemetry_cells)} telemetry cells)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
