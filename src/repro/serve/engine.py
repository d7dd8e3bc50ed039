"""Serving engine: continuous-batched decode with ABFT detect->recompute
recovery, built around a **vectorized per-slot position cursor** and an
optional **paged KV cache** (block-table memory manager).

Executor hierarchy (this module is the FACADE)
----------------------------------------------
The engine is three layers behind one public class:

  * ``serve/scheduler.py`` — host-side request/slot/block bookkeeping:
    ``Request``/``ChunkCursor`` lifecycle, ``EngineStats``, admission
    screening (budget checks, paged allocation, prefix matching + COW
    planning, bounded head-of-line lookahead), chunk-cursor queue, and
    the paged decode growth guard.  Pure host state, mutated strictly
    outside the jitted attempt/retry window.
  * ``serve/runner.py`` — the jitted device entry points (``decode``,
    ``prefill``, ``prefill_prefix``, ``prefill_chunk``) plus the
    slot-masked sampler.  No request state, no mesh awareness.
  * ``serve/executor.py`` — device residency: params, cache, PRNG keys,
    and the hardware-aware ``ProtectionPlan``.  ``LocalExecutor`` is
    the single-device default; ``MeshExecutor`` (``mesh=`` kwarg) runs
    tensor-parallel SPMD over a ``(data=1, model=k)`` device mesh with
    the production sharding rules (``distributed/sharding.py``): params
    sharded by ``param_specs``, the paged block pool's kv-head dim
    sharded by ``cache_specs`` behind ONE logical host block table, and
    the SAME jitted runner functions parallelized by GSPMD propagation
    from the committed inputs.

``ServeEngine`` orchestrates the three: the detect->retry windows, the
per-step intensity-guided selection, telemetry sync, and the public
``admit``/``step``/``run``/``cache_stats`` API are unchanged from the
monolith — as are greedy token streams, byte-for-byte, at every mesh
width (bf16: per-device partials accumulate in f32 and round below the
output precision).

Sharded protection plans
------------------------
With ``mesh=k``, the executor compiles the ``ProtectionPlan`` from the
POST-SHARDING per-device GEMM shapes (``model_parallel=k`` divides the
column-parallel n dims and row-parallel k dims).  Smaller per-device
GEMMs sit lower on the roofline, so the same layer can be compute-bound
(global ABFT) at TP=1 and memory-bound (fused block ABFT) at TP=4 on
the same hardware — the paper's intensity-guided selection re-made per
shard.  The per-step ``for_step`` fast path, the chunk-budget
autotuner, and the telemetry ``scheme_flip``/plan-row events all see
the sharded shapes.

Cache kinds
-----------
``cache_kind="dense"`` (default): every slot owns a dense ``(max_len,)``
cache row — one long request makes the whole batch pay max-length memory.

``cache_kind="paged"``: attention KV lives in fixed-size blocks drawn from
a shared pool (serve/paged_cache.py).  Blocks are allocated at admission
(prompt length only), grown one block at a time as decode crosses block
boundaries, and returned to the free list when a request finishes or is
evicted — including hard-fault eviction under ``RecoveryPolicy``.  Pool
exhaustion never crashes: a request that could NEVER fit is rejected with
``error="oom:block_pool"``; one that merely hit transient pressure
(blocks held by in-flight requests) is deferred until decode frees
blocks; a slot whose mid-decode growth cannot be covered is evicted with
``error="oom:kv_blocks"``.
Token streams are identical to the dense engine under greedy decoding
(block-size divides max_len => identical attention shapes); the allocation
is what changes: ``cache_stats()`` reports pool bytes ≪ slots × max_len
when prompt lengths are skewed.

``prefix_sharing=True`` (paged only) adds refcounted prefix sharing with
copy-on-write: admission matches each prompt against a content-hash index
of resident blocks (``PrefixIndex``), aliases the new slot's leading
table entries onto the longest cached prefix (full blocks refcounted; a
partial tail block is COW-copied because the suffix will write into it),
and prefills ONLY the unshared suffix at its true logical positions.
Matches are capped at ``len(prompt) - 1`` tokens so the suffix always
yields the first sampled token's logits.  The index registers prompts
only after their prefill passed the ABFT check, and entries are purged
when blocks are physically freed — so fault-driven eviction of one
sharer never frees or corrupts blocks a live request still references
(refcounts drop; the free list only sees count-zero blocks).  Greedy
streams are byte-identical to the unshared paged engine: identical
tokens at identical logical positions produce bit-identical KV, and the
suffix path's gathered-KV attention masks padding to exact zeros.
Requires ``model.supports_prefix_sharing`` (attention-only stacks —
SSM/cross-attention state is not a pure function of the token prefix).

Chunked-prefill scheduler (``chunk_tokens``)
--------------------------------------------
Unchunked, ``admit()`` runs the WHOLE prompt's prefill synchronously on
the decode path — a 32k prompt stalls every resident decode stream for
one monolithic model call (the ROADMAP's "async admission" item).  With
``chunk_tokens=N`` set, admission only *allocates* (slot, blocks, prefix
plan, COW) and parks the prompt behind a resumable **chunk cursor**;
``step()`` then builds every iteration from the fixed token budget:

  * all resident decode tokens are packed FIRST — every active stream
    advances every step, so a flood of long prompts can never starve a
    resident decode (the scheduler's latency contract);
  * the remaining ``N - n_decode`` tokens are filled with prefill chunks
    drawn FIFO from the cursor queue, each chunk resuming at its prompt's
    logical position (per-chunk rotary offsets, per-row causal
    ``q_offset``, cache scatter at arbitrary starts — the PR-3
    ``prefix_lens`` machinery generalized to both paged AND dense
    caches).

This subsumes async admission without threads: chunking bounds the
prefill work co-scheduled with every decode step, so TTFT/ITL tails
collapse on long-prompt mixes while greedy streams stay byte-identical
to the unchunked engine (same logical positions => bit-identical KV and
logits; the equivalence tests demand it, faults included).  A fault
detected during a chunk retries ONLY that chunk from the pre-chunk
cache; the step's decode call and earlier chunks are never re-executed.
Requires ``model.supports_chunked_prefill`` (attention-only stacks —
SSM recurrence state cannot resume mid-prompt through the prefill path).

Per-step intensity-guided re-selection: the engine compiles a
``ProtectionPlan`` (core/policy.py) for its (model, hardware, serving)
triple at construction; each executed step's ACTUAL token composition
(decode + chunk tokens) goes through the plan's cached
``for_step(decode, prefill)`` fast path — decode-only steps sit deep in
the memory-bound regime (fused block ABFT), mixed steps carrying a
chunk can cross into the compute-bound regime (global ABFT).  The
per-step ``(composition, intensity, scheme)`` decisions are recorded in
``EngineStats.selection_trace``; the jitted calls resolve the scheme
per GEMM shape at trace time, so distinct compositions genuinely execute
distinct schemes (the paper's §5.3 selection re-made at serving time,
per step instead of per static phase).

``chunk_tokens="auto"`` delegates the budget itself to the plan's
roofline autotuner (``plan.tune_chunk_budget``): the smallest per-step
token budget whose mixed-step arithmetic intensity clears the device
CMR (or, when the step geometry cannot reach the CMR, the
maximum-intensity budget under ``max_len``).  The budget re-tunes as
slot occupancy drifts — its floor tracks resident decode tokens so
prefill always progresses — with re-tunes counted in
``EngineStats.chunk_budget_retunes``.

Engine API
----------
``admit(pending)``
    Batched admission: up to ``len(free_slots())`` requests are drawn
    from ``pending`` (IN PLACE — consumed requests are removed), padded
    to a common length, and prefilled in ONE model call **directly into
    their engine cache rows** (per-slot scatter + per-row length masking
    — no 1-deep temp cache or splice).  Each consumed request is
    admitted, finished (``max_new_tokens`` already satisfied by the
    prefill-sampled token), rejected with ``error`` set before prefill
    (over-long prompt, pool exhaustion), or evicted on a persistent
    prefill fault.  Returns the list of consumed requests so the caller
    can always make progress (no livelock on a hard-faulting head).

    Head-of-line blocking: a transiently-deferred large prompt no longer
    stalls every request behind it.  A bounded lookahead admits later
    requests that fit RIGHT NOW, but each such admission spends one unit
    of the head's bypass budget (``admit_lookahead``); once the budget is
    exhausted, admission reverts to strict FIFO — every freed block is
    implicitly reserved for the deferred head, which therefore cannot
    starve (bounded bypass, then exclusive claim on frees).

``step(fault=None)``
    One decode step for all active slots.  Tokens are chosen by a
    slot-masked sampler inside the jitted step — greedy argmax by default,
    or temperature/top-k sampling driven by a ``(slots,)`` per-slot PRNG
    key vector (each slot owns an independent key stream, advanced only
    on *accepted* steps so a fault retry resamples the same token).

``run(requests, fault_at=None, admit_fault_at=None)``
    Drives admission + decode to completion.  ``fault_at=(step, fault)``
    injects a campaign fault into one decode step; ``admit_fault_at=
    (uid, fault)`` injects into the admission batch containing that uid.

``cache_stats()``
    Cache geometry/occupancy introspection (kind, bytes, block pool
    usage) so benchmarks and tests never poke at private pytrees.

Recovery policy
---------------
``RecoveryPolicy`` makes the paper's detect->recompute loop explicit:

  * a detected fault re-executes the step from the pre-step cache state
    (``prev_cache`` is held until the flag is read back) up to
    ``max_retries`` times — prefill retries likewise restart from the
    pre-admission cache, never from the possibly-corrupted attempt.
    Under paging this stays sound because pool updates are functional
    and the host block tables are mutated only *outside* the
    attempt/retry window (alloc/growth before the step, frees after);
  * if the flag persists, the fault is *hard*: with
    ``evict_on_hard_fault`` (default) the affected requests are evicted
    with ``error`` recorded (their blocks returned to the free list) and
    the engine keeps serving, otherwise a ``RuntimeError`` is raised
    (the seed behavior).

Token budget: ``max_new_tokens`` counts every generated token *including*
the one sampled at prefill, so ``max_new_tokens=N`` yields exactly N new
tokens (``N-1`` decode steps) — a request satisfied at admission never
occupies a slot.

Accounting: ``EngineStats`` distinguishes **rejections** (pre-prefill
screening: ``prompt_too_long``, ``oom:block_pool`` — the request never
held cache state) from **evictions** (a resident request lost its slot:
hard fault, ``oom:kv_blocks`` growth failure).  ``cache_stats()`` reports
paged ``utilization`` against *allocated* tokens (``blocks_used *
block_size``), so internal fragmentation is visible as its complement
rather than hidden by the total-pool denominator, plus ``fragmentation``,
``blocks_shared``, and ``prefix_hit_rate``.

Telemetry (``telemetry=EngineTelemetry(...)``, repro/obs/)
----------------------------------------------------------
An attached ``EngineTelemetry`` exports the engine's internals without
changing them: every ``EngineStats`` counter is mirrored into the
metrics registry after each ``admit()``/``step()`` (monotonic
``inc_to`` — the exported counters equal the stats fields exactly, by
construction), per-step deltas feed the rolling ``FaultRateMonitor``
(the observed detection/retry-rate surface ROADMAP 5b's adaptive
protection consumes), and — when tracing is enabled — the spans below
are recorded as Chrome-trace JSON, plus instant events for fault
detections, evictions/rejections, intensity-guided ``scheme_flip``s
carrying {intensity, scheme, decode, prefill, model_parallel}, and one
``plan_row`` instant per protection-plan entry at attach time (the
per-shard plan surface).

Spans (repro/obs/trace.py) are host phases.  Every engine opens them,
telemetry or not, and they land in any ``jax.profiler`` trace on the
device's clock; an enabled tracer also records them, under the same
names, on its own clock.  No span syncs the device: device work shows
as time in the ``wait`` spans, the readbacks the engine makes anyway.

  serve.step {decode, prefill}       one step() (tokens it served)
    serve.schedule                   adaptation, fault poll, chunk
                                     budget and plan, paged growth
    serve.cow_copy {pairs}           copy-on-write block moves
    serve.<call> {rows, shape, uid}  call in decode | chunk | verify
      serve.<call>.prepare           host arrays, host->device copies
                                     (fault operands too), table and
                                     key gathers
      serve.<call>.dispatch          the jitted call until it returns
      serve.<call>.wait {what}       blocking readback: flag | tokens
      serve.retry {call}             a detected fault's re-execution
                                     (dispatch + flag wait)
      serve.<call>.commit            cache/key rebinds, token stamps,
                                     request and slot bookkeeping
    serve.account                    selection record, telemetry sync
  serve.admit {consumed, admitted}   one admit() (request counts)
    serve.schedule                   admission screening
    serve.prefill {rows, shape, uid} whole-prompt prefill (unchunked),
                                     with the same children
    serve.account

``uid`` is the first row's request; ``shape`` is the padded
rows x tokens of the call.  Telemetry is passive: greedy token streams
are byte-identical with it enabled or disabled.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import ErrorAdaptivePolicy
from repro.core.protected import ABFTConfig
from repro.models.layers import LayerCtx, ModelFault
from repro.models.model import Model
from repro.obs.trace import Tracer
from repro.serve.executor import LocalExecutor, MeshExecutor
from repro.serve.paged_cache import (
    BlockPool,
    PrefixIndex,
    pytree_bytes,
)
from repro.serve.runner import ModelRunner
from repro.serve.scheduler import (
    PRE_PREFILL_ERRORS,
    ChunkCursor,
    EngineStats,
    RecoveryPolicy,
    Request,
    Scheduler,
    _pad_len,
    _pad_rows,
)
from repro.serve.spec_decode import (
    greedy_accept,
    make_proposer,
    rejection_sample,
    target_probs,
)

__all__ = [
    "ServeEngine", "Request", "RecoveryPolicy", "EngineStats",
    "ChunkCursor", "PRE_PREFILL_ERRORS",
]

# tracer of engines without telemetry: its spans reach a recording
# jax.profiler trace, and it keeps no in-memory record
_DEFAULT_TRACER = Tracer(enabled=False)


def _pytrees_equal(a, b) -> bool:
    """Exact leaf-wise equality of two pytrees (the shadow-stream state
    comparison — bit-identical or not, no tolerance)."""
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


class ServeEngine:
    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 abft: ABFTConfig = ABFTConfig(), dtype=jnp.bfloat16,
                 hints=None, mesh=None,
                 policy: RecoveryPolicy = RecoveryPolicy(),
                 cache_kind: str = "dense", block_size: int = 16,
                 num_blocks: int | None = None,
                 prefix_sharing: bool = False, admit_lookahead: int = 8,
                 chunk_tokens: int | str | None = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 telemetry=None, fault_model=None,
                 classify_injections: bool | None = None,
                 spec_decode=None, draft_len: int | str | None = None,
                 draft_window: int = 8, draft_units: int = 1):
        assert slots >= 1
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.abft = abft
        self.policy = policy
        # campaign injection (core/faults.FaultModel): polled once per
        # step() for this step's fault; every injected fault — campaign
        # or hand-armed — is placement-recorded, and when classification
        # is on (default: whenever a fault model is attached) undetected
        # faults are shadow-checked for silent corruption
        self.fault_model = fault_model
        self.classify_injections = bool(
            classify_injections if classify_injections is not None
            else fault_model is not None)
        self._injection_meta: dict | None = None
        self.cache_kind = cache_kind
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.admit_lookahead = int(admit_lookahead)
        # --- executor layer: device residency (params/cache/keys) and
        # the hardware-aware per-shard protection plan.  mesh=None is
        # the single-device monolith behavior; mesh=k (or a prebuilt
        # Mesh) shards params + paged KV over the 'model' axis.
        if mesh is None:
            self.executor = LocalExecutor(model, params, dtype=dtype,
                                          hints=hints)
        else:
            self.executor = MeshExecutor(model, params, mesh=mesh,
                                         dtype=dtype, hints=hints)
            if abft.runs_kernels:
                # a Pallas kernel is not partitioned by GSPMD: kernel
                # schemes run per shard (core/protected.py::_per_shard)
                abft = dataclasses.replace(abft, mesh=self.executor.mesh)
                self.abft = abft
        # --- error-rate-adaptive protection (ErrorAdaptivePolicy):
        # schemes resolve at TRACE time from the LayerCtx's config, so a
        # runtime level change cannot ride one mutable policy inside one
        # runner — the engine compiles BOTH levels up front (immutable
        # per-level config/ctx/plan/runner) and swaps the active set when
        # update() crosses a threshold (_set_protection_level)
        eff = abft.effective_policy()
        self.adaptive = eff if isinstance(eff, ErrorAdaptivePolicy) \
            else None
        if self.adaptive is not None:
            level_cfgs = (
                dataclasses.replace(abft, policy=self.adaptive.base),
                dataclasses.replace(abft, policy=self.adaptive.escalated))
        else:
            level_cfgs = (abft,)
        self._level_abft = level_cfgs
        self._level_ctx = tuple(
            LayerCtx(abft=c, hints=self.executor.hints)
            for c in level_cfgs)
        self.protection_level = 0
        self.ctx = self._level_ctx[0]
        self._dtype_bytes = self.executor.dtype_bytes
        # observability (repro/obs): optional EngineTelemetry — metrics
        # mirroring + fault-rate monitor + span tracer.  _tr is always a
        # Tracer so instrumented paths need no None checks; _last_scheme
        # tracks the per-step selection for scheme_flip instant events.
        # The adaptive policy consumes the fault-rate monitor, so an
        # adaptive engine gets a (trace-off) telemetry object implicitly.
        if telemetry is None and self.adaptive is not None:
            from repro.obs.telemetry import EngineTelemetry

            telemetry = EngineTelemetry()
        self.telemetry = telemetry
        self._tr = telemetry.tracer if telemetry is not None \
            else _DEFAULT_TRACER
        self._last_scheme: str | None = None
        # compiled protection plan for this (model, hardware, serving,
        # shard) tuple: per-device GEMM shapes under the executor's
        # model_parallel width drive the intensity-guided selection —
        # the per-step fast path step() consults plus the roofline
        # chunk-budget autotuner (core/policy.py).  One plan per
        # protection level (they differ exactly when escalation does).
        self._level_plans = tuple(
            self.executor.protection_plan(c, slots=slots)
            for c in level_cfgs)
        self.plan = self._level_plans[0]
        # chunked-prefill scheduler: per-step token budget + chunk cursors.
        # chunk_tokens="auto" asks the plan for the smallest budget whose
        # mixed-step arithmetic intensity clears the device CMR (ROADMAP
        # autotuning item); the budget re-tunes as slot occupancy drifts
        # (_retune_chunk_budget).
        self.chunk_auto = chunk_tokens == "auto"
        if self.chunk_auto:
            chunk_tokens = self.plan.tune_chunk_budget(lo=8, hi=max_len)
        if chunk_tokens is not None:
            if not isinstance(chunk_tokens, int):
                raise ValueError(
                    f"chunk_tokens must be an int or 'auto', got "
                    f"{chunk_tokens!r}")
            if chunk_tokens < 1:
                raise ValueError("chunk_tokens must be >= 1")
            if not model.supports_chunked_prefill:
                raise ValueError(
                    "chunk_tokens requires an attention-only decoder "
                    "(SSM / cross-attention state cannot resume a prompt "
                    "mid-sequence)")
        self.chunk_tokens = chunk_tokens
        # pre-escalation budget, restored on de-escalation (the adaptive
        # policy's shrink_chunk scales it while escalated)
        self._chunk_tokens_base = chunk_tokens \
            if isinstance(chunk_tokens, int) else None
        # admission-campaign fault awaiting the target's first chunk
        self._pending_prefill_fault: tuple | None = None

        if cache_kind == "paged":
            width = -(-max_len // block_size)         # blocks covering max_len
            if num_blocks is None:
                num_blocks = slots * width            # dense-equivalent pool
            pool: BlockPool | None = BlockPool(
                num_blocks, block_size, slots, width)
            self.executor.init_paged_cache(slots, num_blocks, block_size)
        elif cache_kind == "dense":
            pool = None
            self.executor.init_dense_cache(slots, max_len)
        else:
            raise ValueError(f"unknown cache_kind {cache_kind!r}")

        if prefix_sharing:
            if pool is None:
                raise ValueError("prefix_sharing requires cache_kind='paged'")
            if not model.supports_prefix_sharing:
                raise ValueError(
                    "prefix_sharing requires an attention-only decoder "
                    "(no SSM / cross-attention state outside the block "
                    "pool)")
            index: PrefixIndex | None = PrefixIndex(block_size)
        else:
            index = None

        # --- scheduler layer: host-side slot/block/request bookkeeping
        self.scheduler = Scheduler(
            slots=slots, max_len=max_len, admit_lookahead=admit_lookahead,
            stats=EngineStats(), tracer=self._tr, pool=pool, index=index)
        # --- runner layer: the jitted device entry points, one runner
        # per protection level (jit compilation is lazy, so the inactive
        # level costs nothing until first escalation)
        self._level_runners = tuple(
            ModelRunner(model, ctx, temperature=temperature, top_k=top_k)
            for ctx in self._level_ctx)
        self.runner = self._level_runners[0]
        # the audit (analysis/audit.py) and the equivalence tests trace
        # these attributes by name; they alias the runner's compiled fns
        self._decode = self.runner.decode
        self._prefill = self.runner.prefill
        self._prefill_prefix = self.runner.prefill_prefix
        self._prefill_chunk = self.runner.prefill_chunk
        self._verify = self.runner.verify

        # --- speculative decoding (serve/spec_decode.py): a draft
        # proposer plus the per-step draft length K.  Verification is
        # the integrity boundary — drafts run unprotected (a wrong or
        # corrupted draft costs throughput, never correctness), while
        # the K+1-token verify call goes through the same ABFT-checked
        # jitted path and detect->retry window as decode.  draft_len
        # "auto"/None picks K from the SAME roofline that selects
        # schemes (plan.tune_draft_len) and re-tunes as occupancy
        # drifts; a fixed int is shrunk by the adaptive policy's
        # shrink_draft while escalated.
        self.spec = None
        self.draft_len = 0
        self.draft_auto = draft_len in (None, "auto")
        self._draft_len_base: int | None = None
        self._last_decode_tokens = 0
        if spec_decode is not None:
            if not model.supports_chunked_prefill:
                raise ValueError(
                    "spec_decode requires an attention-only decoder "
                    "(SSM recurrence cannot roll back to the last "
                    "accepted position)")
            if abft.flash_attention:
                raise ValueError(
                    "spec_decode requires the XLA attention path: the "
                    "fused flash_decode kernel cannot reproduce the "
                    "multi-token verify stream bit-for-bit (the greedy "
                    "byte-equality gate)")
            if self.draft_auto:
                self.draft_len = max(1, self.plan.tune_draft_len(
                    batch=slots))
            else:
                if not isinstance(draft_len, int) or draft_len < 1:
                    raise ValueError(
                        f"draft_len must be a positive int or 'auto', "
                        f"got {draft_len!r}")
                self.draft_len = draft_len
                self._draft_len_base = draft_len
            self.spec = make_proposer(
                spec_decode, model, self._level_ctx[0],
                lambda: self.params, units=draft_units,
                window=draft_window)

        self.executor.init_keys(seed, slots)
        self._emit_plan_rows()

    # ------------------------------------------- component state facade
    # The monolith's attribute surface is preserved verbatim: tests,
    # benchmarks, and the coverage audit read (and some write) these.
    @property
    def params(self):
        return self.executor.params

    @property
    def cache(self):
        return self.executor.cache

    @cache.setter
    def cache(self, value):
        self.executor.cache = value

    @property
    def keys(self):
        return self.executor.keys

    @keys.setter
    def keys(self, value):
        self.executor.keys = value

    @property
    def mesh(self):
        return self.executor.mesh

    @property
    def model_parallel(self) -> int:
        return self.executor.model_parallel

    @property
    def stats(self) -> EngineStats:
        return self.scheduler.stats

    @stats.setter
    def stats(self, value: EngineStats) -> None:
        self.scheduler.stats = value

    @property
    def pos(self):
        return self.scheduler.pos

    @property
    def active(self) -> dict:
        return self.scheduler.active

    @property
    def pool(self):
        return self.scheduler.pool

    @property
    def index(self):
        return self.scheduler.index

    @index.setter
    def index(self, value) -> None:
        self.scheduler.index = value

    @property
    def _prefill_cursors(self) -> dict:
        return self.scheduler.prefill_cursors

    # ----------------------------------------------------------- telemetry
    def attach_telemetry(self, telemetry) -> None:
        """Attach (or replace) an ``EngineTelemetry`` mid-lifecycle —
        e.g. after a warm-up run whose stats were reset, so the mirrored
        counters start from the fresh ``EngineStats``.  The telemetry
        object must be fresh too (counter mirroring is monotonic)."""
        self.telemetry = telemetry
        self._tr = telemetry.tracer if telemetry is not None \
            else _DEFAULT_TRACER
        self.scheduler.tracer = self._tr
        self._emit_plan_rows()

    def _emit_plan_rows(self) -> None:
        """Export the compiled (per-shard) protection plan as one
        ``plan_row`` instant per entry — a tracing consumer sees WHICH
        scheme each GEMM site runs under this executor's model_parallel
        width (the sharded-plan surface ISSUE 8 asks for)."""
        if not self._tr.enabled:
            return
        for row in self.plan.report_rows():
            args = {"model_parallel": self.model_parallel,
                    "protection_level": self.protection_level}
            if getattr(self, "spec", None) is not None:
                args["draft_len"] = self.draft_len
            args.update(row)
            self._tr.instant("plan_row", args)

    # ------------------------------------------- adaptive protection
    def _set_protection_level(self, level: int, evidence: dict) -> None:
        """Swap the active (ctx, plan, runner) set to ``level`` — the
        runtime half of ErrorAdaptivePolicy.  Emits a
        ``protection_escalation`` instant carrying the rate evidence,
        re-emits plan rows at the new level, optionally shrinks the
        chunk budget while escalated, and re-baselines the fault-rate
        monitor so the new regime is judged on fresh observations."""
        self.protection_level = level
        self.ctx = self._level_ctx[level]
        self.plan = self._level_plans[level]
        self.runner = self._level_runners[level]
        self._decode = self.runner.decode
        self._prefill = self.runner.prefill
        self._prefill_prefix = self.runner.prefill_prefix
        self._prefill_chunk = self.runner.prefill_chunk
        self._verify = self.runner.verify
        if level:
            self.stats.protection_escalations += 1
        else:
            self.stats.protection_deescalations += 1
        if self._chunk_tokens_base is not None and not self.chunk_auto \
                and self.adaptive is not None:
            if level and self.adaptive.shrink_chunk < 1.0:
                self.chunk_tokens = max(8, (int(
                    self._chunk_tokens_base * self.adaptive.shrink_chunk)
                    // 8) * 8)
            else:
                self.chunk_tokens = self._chunk_tokens_base
        # fixed draft lengths shrink under escalation like the chunk
        # budget: a shorter draft window is a smaller verify-retry blast
        # radius (auto draft lengths re-tune per step and apply the
        # shrink there)
        if self._draft_len_base is not None and self.adaptive is not None:
            if level and self.adaptive.shrink_draft < 1.0:
                self.draft_len = max(1, int(
                    self._draft_len_base * self.adaptive.shrink_draft))
            else:
                self.draft_len = self._draft_len_base
        args = {"level": level,
                "direction": "escalate" if level else "deescalate"}
        for k in ("window_detection_rate", "window_hard_fault_rate",
                  "ewma_detections_per_step",
                  "ewma_hard_faults_per_step"):
            if k in evidence:
                args[k] = evidence[k]
        self._tr.instant("protection_escalation", args)
        self._emit_plan_rows()
        if self.telemetry is not None:
            # keep lifetime totals; clear window + EWMA (the audit trail
            # survives — FaultRateMonitor.reset's contract)
            self.telemetry.faults.reset()

    def _maybe_adapt(self) -> None:
        """Per-step adaptation decision: feed the observed fault-rate
        snapshot to the ErrorAdaptivePolicy and swap protection levels
        when it says so.  No-op for non-adaptive engines."""
        if self.adaptive is None or self.telemetry is None:
            return
        snap = self.telemetry.faults.snapshot()
        if self.adaptive.update(snap):
            self._set_protection_level(self.adaptive.level, snap)

    # ------------------------------------------- injection bookkeeping
    def _take_injection_meta(self, default_source: str) -> dict:
        """Claim the pending injection metadata (set by step()/run() for
        campaign and fault_at injections) or synthesize one for a
        directly-passed fault."""
        meta = self._injection_meta
        self._injection_meta = None
        if meta is None:
            meta = {"source": default_source, "kind": "manual"}
        return meta

    def _record_injection(self, meta: dict, phase: str, outcome: str,
                          **extra) -> None:
        """Ground truth for one executed injection: where it landed
        (engine step + phase) and how it resolved (corrected /
        uncorrected / sdc / masked / undetected)."""
        entry = dict(meta)
        entry["engine_step"] = self.stats.steps
        entry["phase"] = phase
        entry["outcome"] = outcome
        entry.update(extra)
        self.stats.record_injection(entry)
        self._tr.instant("fault_injected", {
            "phase": phase, "outcome": outcome,
            "kind": entry.get("kind"), "source": entry.get("source")})

    def _shadow_outcome(self, emitted, state, shadow) -> tuple:
        """Classify an UNDETECTED injection by shadow comparison: re-run
        the same jitted call clean from the pre-step state and compare.
        SDC means the emitted tokens differ (user-visible silent
        corruption); tokens-equal is 'masked' (the fault landed out of
        range or perturbed state below the detection threshold — the
        entry still records whether internal state matched)."""
        s_emitted, s_state = shadow
        tokens_match = bool(jnp.array_equal(emitted, s_emitted))
        state_match = _pytrees_equal(state, s_state)
        outcome = "masked" if tokens_match else "sdc"
        return outcome, {"tokens_match": tokens_match,
                         "state_match": state_match}

    def _sync_telemetry(self) -> None:
        """Mirror EngineStats into the registry + feed the fault-rate
        monitor (one observation per admit/step)."""
        if self.telemetry is None:
            return
        self.telemetry.sync(
            self.stats,
            active_slots=len(self.active),
            prefill_cursors=len(self._prefill_cursors),
            blocks_used=(self.pool.blocks_used
                         if self.pool is not None else None),
            blocks_free=(self.pool.blocks_free
                         if self.pool is not None else None),
            chunk_budget=(self.chunk_tokens
                          if isinstance(self.chunk_tokens, int)
                          else None),
            draft_len=(self.draft_len
                       if self.spec is not None else None))

    # ------------------------------------------------------------ admission
    def free_slots(self) -> list:
        return self.scheduler.free_slots()

    def _release(self, slot: int) -> None:
        self.scheduler.release(slot)

    def _finish(self, req: Request, error: str | None = None, *,
                reject: bool = False, evict: bool = False) -> None:
        self.scheduler.finish(req, error, reject=reject, evict=evict)

    def _drain_finished(self) -> list:
        return self.scheduler.drain_finished()

    def _copy_cow_blocks(self, cow_pairs: list) -> None:
        """Commit COW payload moves BEFORE any jitted attempt so the
        detect->retry window sees stable tables and block contents
        (plain data movement, not an ABFT-protected GEMM)."""
        if not cow_pairs:
            return
        with self._tr.span("serve.cow_copy", {"pairs": len(cow_pairs)}):
            self.cache = self.model.copy_paged_blocks(
                self.cache, [s for s, _ in cow_pairs],
                [d for _, d in cow_pairs])
        self.stats.cow_copies += len(cow_pairs)

    def admit(self, pending: list, fault: ModelFault | None = None,
              fault_uid: int | None = None) -> list:
        """Batched admission (see module docstring).  Consumes up to
        ``len(free_slots())`` requests from ``pending`` — IN PLACE — and
        returns the consumed requests: every one ends up active, done, or
        rejected/evicted with ``error`` set, so the caller always
        progresses.  Consumption is FIFO except for the bounded lookahead
        past a transiently-deferred head (see module docstring).
        ``fault``/``fault_uid``: campaign injection applied only when the
        targeted request actually reaches prefill."""
        with self._tr.span("serve.admit") as sp:
            consumed = self._admit_impl(pending, fault, fault_uid)
            sp.set_args(consumed=len(consumed),
                        admitted=len([r for r in consumed
                                      if r.error is None]))
            with self._tr.span("serve.account"):
                self._sync_telemetry()
        return consumed

    def _admit_impl(self, pending: list, fault: ModelFault | None = None,
                    fault_uid: int | None = None) -> list:
        with self._tr.span("serve.schedule"):
            batch = self.scheduler.select_admission(pending)
        admitted, slot_list = batch.admitted, batch.slot_list
        if not admitted:
            return batch.consumed
        if fault is not None and fault_uid is not None and not any(
                r.uid == fault_uid for r in admitted):
            fault = None    # campaign target never reached prefill

        if self.chunk_tokens is not None:
            # chunked-prefill admission: allocation only — NO model call,
            # so a 32k prompt costs the decode path nothing here.  The
            # prompt becomes a chunk cursor; step() co-schedules its
            # chunks against resident decodes under the token budget.
            self._copy_cow_blocks(batch.cow_pairs)
            self.scheduler.park_prefill(batch)
            if fault is not None and fault_uid is not None:
                # campaign injection fires at the target's first chunk
                self._pending_prefill_fault = (fault_uid, fault)
            return batch.consumed

        tr = self._tr
        with tr.span("serve.prefill", {"rows": len(admitted),
                                       "uid": admitted[0].uid}) as call:
            with tr.span("serve.prefill.prepare"):
                slot_ids = np.asarray(slot_list, np.int32)
                full_lens = np.asarray([len(r.prompt) for r in admitted],
                                       np.int32)
                prefix = np.asarray(
                    [p.match_len if p is not None else 0
                     for p in batch.prefix_plans], np.int32)
                lengths = full_lens - prefix   # valid SUFFIX tokens per row
                # admissible prompts always fit (budget check above), so
                # clamping the bucketed pad to max_len keeps the scatter
                # in bounds
                Lpad = min(_pad_len(int(lengths.max())), self.max_len)
                toks = np.zeros((len(admitted), Lpad), np.int32)
                for i, r in enumerate(admitted):
                    toks[i, : lengths[i]] = r.prompt[prefix[i]:]

                # COW payload moves are committed BEFORE the attempt so
                # the detect->retry window sees stable tables and block
                # contents
                self._copy_cow_blocks(batch.cow_pairs)

                tables = (self.pool.device_tables(slot_ids)
                          if self.pool is not None else None)
                keys = self.keys[jnp.asarray(slot_ids)]
                use_prefix = bool(prefix.any())
                args = (self.params, jnp.asarray(toks),
                        jnp.asarray(slot_ids), jnp.asarray(lengths))
                prefix_dev = jnp.asarray(prefix)
                prev_cache = self.cache  # pre-admission state, for retry
                f = fault if fault is not None else ModelFault.none()
                meta = self._take_injection_meta("admit_fault") \
                    if fault is not None else None
            call.set_args(shape=f"{len(admitted)}x{Lpad}")

            def attempt(fa):
                if use_prefix:
                    return self._prefill_prefix(
                        args[0], args[1], prev_cache, args[2], args[3],
                        keys, tables, prefix_dev, fa)
                return self._prefill(
                    args[0], args[1], prev_cache, args[2], args[3], keys,
                    tables, fa)

            with tr.span("serve.prefill.dispatch"):
                first, new_cache, flag, nkeys = attempt(f)
            with tr.span("serve.prefill.wait", {"what": "flag"}):
                faulted = bool(flag)
            if faulted:
                self.stats.faults_detected += 1
                tr.instant("fault_detected", {"phase": "prefill"})
                for _ in range(self.policy.max_retries):
                    self.stats.retries += 1
                    # clean retry from the PRE-admission cache — never
                    # from the possibly-corrupted attempt (mirrors
                    # decode's prev_cache); same keys, so the retry
                    # resamples the same token
                    with tr.span("serve.retry", {"call": "prefill"}):
                        with tr.span("serve.prefill.dispatch"):
                            first, new_cache, flag, nkeys = attempt(
                                ModelFault.none())
                        with tr.span("serve.prefill.wait",
                                     {"what": "flag"}):
                            faulted = bool(flag)
                    if not faulted:
                        break
                if meta is not None:
                    self._record_injection(
                        meta, "prefill",
                        "uncorrected" if faulted else "corrected")
                if faulted:
                    # persistent fault: evict the admission batch with
                    # recorded errors instead of retrying it forever
                    # (livelock fix).  _release drops refcounts only — a
                    # shared prefix block a LIVE request still references
                    # stays resident
                    self.stats.hard_faults += 1
                    tr.instant("hard_fault", {"phase": "prefill"})
                    for slot, r in zip(slot_ids, admitted):
                        self._finish(r, "hard_fault:prefill", evict=True)
                        self._release(int(slot))
                    return batch.consumed
            elif meta is not None:
                outcome, extra = ("undetected", {})
                if self.classify_injections:
                    s_first, s_cache, _, _ = attempt(ModelFault.none())
                    outcome, extra = self._shadow_outcome(
                        first, new_cache, (s_first, s_cache))
                self._record_injection(meta, "prefill", outcome, **extra)

            with tr.span("serve.prefill.wait", {"what": "tokens"}):
                first = np.asarray(first)
            with tr.span("serve.prefill.commit"):
                self.cache = new_cache
                self.keys = self.keys.at[jnp.asarray(slot_ids)].set(nkeys)
                # admit-time monolithic prefill is a prefill-only "step"
                # in the selection trace: the whole-prompt token mass
                # lands in one call (exactly the composition the chunked
                # scheduler bounds)
                self._observe_step_mix(0, int(lengths.sum()))
                now = time.perf_counter()
                for i, (slot, req) in enumerate(zip(slot_ids, admitted)):
                    req.generated.append(int(first[i]))
                    req.times.append(now)
                    self.stats.tokens += 1
                    self.stats.prompt_tokens_total += int(full_lens[i])
                    self.stats.prefix_tokens_shared += int(prefix[i])
                    if len(req.generated) >= req.max_new_tokens:
                        self._finish(req)         # budget met at prefill:
                        self._release(int(slot))  # the request never
                        continue                  # occupies a slot
                    self.active[int(slot)] = req
                    self.pos[int(slot)] = int(full_lens[i])
                    if self.index is not None:
                        # register only AFTER the flag read back clean:
                        # the index must never name blocks holding a
                        # faulty attempt's data
                        self.index.add(req.prompt,
                                       self.pool.tables[int(slot)])
        return batch.consumed

    # ------------------------------------------------------------ decoding
    def step(self, fault: ModelFault | None = None) -> dict:
        """One engine step.  Returns {uid: token} for decoded slots.

        Unchunked: one decode step for all active slots (admission
        already prefilled them whole).  Chunked (``chunk_tokens`` set):
        one *budgeted* step — all resident decode tokens first, then the
        leftover budget is filled with prefill chunks from the cursor
        queue (see module docstring).

        With a ``fault_model`` attached and no explicit ``fault``, the
        campaign process is polled for this step's injection (an
        explicit fault takes precedence and leaves the campaign clock
        untouched).  An adaptive policy re-evaluates the protection
        level from the observed fault rates BEFORE the step executes."""
        before = self.stats.steps
        t0 = time.perf_counter()
        tr = self._tr
        with tr.span("serve.step") as sp:
            with tr.span("serve.schedule"):
                self._maybe_adapt()
                if fault is None and self.fault_model is not None:
                    ev = self.fault_model.poll()
                    if ev is not None:
                        fault = ev.model_fault
                        self._injection_meta = {"source": "campaign",
                                                **ev.describe()}
                rows = self._plan_chunks() \
                    if self.chunk_tokens is not None else None
                # paged growth/COW guard BEFORE the jitted decode (tables
                # stable across the attempt/retry window); a verify step
                # grows for its draft window once the drafts exist
                cow_pairs = self.scheduler.grow_for_decode() \
                    if self.spec is None else []
            # the COW payload moves the guard planned are committed on
            # device before the first call
            self._copy_cow_blocks(cow_pairs)
            prefill_tokens = 0
            if rows is not None:
                out, prefill_tokens = self._step_chunked(rows, fault)
            else:
                out = self._serve_core(fault)
            # a fault that found no executing call this step (idle
            # engine) corrupted nothing — drop its unclaimed metadata
            self._injection_meta = None
            with tr.span("serve.account"):
                if self.stats.steps > before:
                    self._observe_step_mix(self._last_decode_tokens,
                                           prefill_tokens)
                if self.telemetry is not None:
                    if self.stats.steps > before:
                        self.telemetry.observe_step_latency(
                            time.perf_counter() - t0)
                    self._sync_telemetry()
            sp.set_args(decode=self._last_decode_tokens,
                        prefill=prefill_tokens)
        return out

    def _observe_step_mix(self, decode_tokens: int,
                          prefill_tokens: int) -> None:
        """Record THIS step's intensity-guided (composition, intensity,
        scheme) decision via the plan's cached per-step fast path
        (``plan.for_step``).  The representative dims are the widest
        per-token projection (d_model x d_ff — per-shard under TP); the
        jitted calls re-resolve the scheme per GEMM shape at trace time
        anyway — this records the step-level decision those shapes
        imply."""
        if decode_tokens + prefill_tokens == 0:
            return
        sel = self.plan.for_step(decode_tokens, prefill_tokens)
        self.stats.observe_selection(decode_tokens, prefill_tokens,
                                     sel.arithmetic_intensity,
                                     sel.scheme_name)
        if self._last_scheme is not None and \
                sel.scheme_name != self._last_scheme:
            # the paper's §5.3 decision changed regime between steps —
            # exported as an instant event so a Perfetto timeline shows
            # WHERE the serving mix crossed the CMR boundary
            self.stats.scheme_flips += 1
            self._tr.instant("scheme_flip", {
                "intensity": sel.arithmetic_intensity,
                "scheme": sel.scheme_name,
                "decode": decode_tokens, "prefill": prefill_tokens,
                "model_parallel": self.model_parallel,
            })
        self._last_scheme = sel.scheme_name

    def _retune_chunk_budget(self) -> None:
        """Auto-budget re-tuning as slot occupancy drifts: the budget
        floor tracks resident decode tokens (decode packs first — the
        floor guarantees prefill a quantum of progress every step),
        while the CMR target keeps full mixed steps compute-bound
        whenever the step geometry can reach it."""
        budget = self.plan.tune_chunk_budget(
            decode_tokens=len(self.active), lo=8, hi=self.max_len)
        if budget != self.chunk_tokens:
            self.chunk_tokens = budget
            self.stats.chunk_budget_retunes += 1

    def _plan_chunks(self) -> list:
        """This step's prefill chunks under the token budget left after
        the resident decode tokens (re-tuning an auto budget first)."""
        if self.chunk_auto:
            self._retune_chunk_budget()
        return self.scheduler.plan_chunks(
            max(0, self.chunk_tokens - len(self.active)))

    def _step_chunked(self, rows: list,
                      fault: ModelFault | None = None) -> tuple:
        """One budgeted mixed step over the planned chunk ``rows``:
        decode tokens are packed first (every resident stream advances
        every step — the starvation guarantee), then the prefill chunks
        fill ``chunk_tokens - n_decode``.  An injected step fault lands
        on the prefill chunk when one is scheduled, else on the decode
        call — each call retries independently, so a chunk fault
        re-executes ONLY that chunk.  Returns ({uid: token}, prefill
        tokens served)."""
        prefill_tokens = sum(take for _, _, take, _ in rows)
        chunk_fault = fault if rows else None
        decode_fault = fault if not rows else None

        out = {}
        steps_before = self.stats.steps
        self._last_decode_tokens = 0
        if self.active:
            out = self._serve_core(decode_fault)
        if rows:
            committed = self._run_prefill_chunk(rows, chunk_fault)
            if not committed:
                prefill_tokens = 0     # discarded: never actually served
            if self.stats.steps == steps_before:
                # the chunk ran even if decode didn't (no actives, or the
                # growth guard evicted them all before executing) — count
                # the step so run()'s fault_at disarm check sees it and
                # never re-injects a fault this chunk already consumed
                self.stats.steps += 1
        return out, prefill_tokens

    def _run_prefill_chunk(self, rows: list,
                           fault: ModelFault | None) -> bool:
        """Execute one co-scheduled prefill-chunk batch (host side of the
        chunk state machine).  Cursor/table state mutates only outside
        the attempt/retry window; a detected fault re-executes the chunk
        from the pre-chunk cache — earlier chunks and this step's decode
        are never re-run.  Returns True when the chunk committed, False
        when a persistent fault discarded it (the batch was evicted and
        its tokens were never served)."""
        A = len(rows)
        slot_list = [s for s, _, _, _ in rows]
        # pending admission-campaign fault: consumed by the first chunk
        # batch containing the target (one fault per jitted call — if a
        # step fault is already routed here, the campaign entry is
        # retired rather than left to linger past the target's prefill)
        pending_src = False
        if self._pending_prefill_fault is not None:
            uid, pf = self._pending_prefill_fault
            if any(cur.req.uid == uid for _, cur, _, _ in rows):
                if fault is None:
                    fault = pf
                    pending_src = True
                self._pending_prefill_fault = None
        meta = None
        if fault is not None:
            meta = self._take_injection_meta(
                "admit_fault" if pending_src else "manual")

        tr = self._tr
        Apad = _pad_rows(A, self.slots)
        Lpad = min(_pad_len(max(take for _, _, take, _ in rows)),
                   self.max_len)
        with tr.span("serve.chunk", {"rows": A, "shape": f"{Apad}x{Lpad}",
                                     "uid": rows[0][1].req.uid}):
            with tr.span("serve.chunk.prepare"):
                toks = np.zeros((Apad, Lpad), np.int32)
                slot_ids = np.full((Apad,), slot_list[0], np.int32)
                lengths = np.zeros((Apad,), np.int32)
                starts = np.zeros((Apad,), np.int32)
                final = np.zeros((Apad,), bool)
                for i, (slot, cur, take, fin) in enumerate(rows):
                    toks[i, :take] = cur.req.prompt[
                        cur.filled:cur.filled + take]
                    slot_ids[i] = slot
                    lengths[i] = take
                    starts[i] = cur.filled
                    final[i] = fin
                # padding rows alias row 0's slot with lengths == 0: their
                # cache writes route to the drop sentinel and their
                # sampled token / key advance are masked by ``final`` —
                # pure shape ballast so the jit cache is keyed by (row
                # bucket, length bucket) only
                tables = (self.pool.device_tables(slot_ids)
                          if self.pool is not None else None)
                keys = self.keys[jnp.asarray(slot_ids)]
                prev_cache = self.cache    # pre-chunk state, for retry
                args = (self.params, jnp.asarray(toks),
                        jnp.asarray(slot_ids), jnp.asarray(lengths),
                        jnp.asarray(starts), jnp.asarray(final))
                f = fault if fault is not None else ModelFault.none()
                retry_f = f if (meta is not None
                                and meta.get("kind") == "permanent") \
                    else ModelFault.none()

            def attempt(fa):
                return self._prefill_chunk(
                    args[0], args[1], prev_cache, args[2], args[3], keys,
                    tables, args[4], args[5], fa)

            with tr.span("serve.chunk.dispatch"):
                first, new_cache, flag, nkeys = attempt(f)
            with tr.span("serve.chunk.wait", {"what": "flag"}):
                faulted = bool(flag)
            if faulted:
                self.stats.faults_detected += 1
                tr.instant("fault_detected", {"phase": "prefill_chunk"})
                for _ in range(self.policy.max_retries):
                    self.stats.retries += 1
                    self.stats.chunk_retries += 1
                    with tr.span("serve.retry", {"call": "chunk"}):
                        with tr.span("serve.chunk.dispatch"):
                            first, new_cache, flag, nkeys = attempt(
                                retry_f)
                        with tr.span("serve.chunk.wait", {"what": "flag"}):
                            faulted = bool(flag)
                    if not faulted:
                        break
                if meta is not None:
                    self._record_injection(
                        meta, "prefill_chunk",
                        "uncorrected" if faulted else "corrected")
                if faulted:
                    # persistent chunk fault: evict ONLY this chunk
                    # batch's requests (their earlier chunks die with
                    # their blocks — refcounts protect any shared prefix
                    # a live sharer holds); the committed cache stays
                    # pre-chunk
                    self.stats.hard_faults += 1
                    tr.instant("hard_fault", {"phase": "prefill_chunk"})
                    for slot, cur, _, _ in rows:
                        self._finish(cur.req, "hard_fault:prefill",
                                     evict=True)
                        del self._prefill_cursors[slot]
                        self._release(slot)
                        if self._pending_prefill_fault is not None and \
                                self._pending_prefill_fault[0] == \
                                cur.req.uid:
                            self._pending_prefill_fault = None  # gone
                    return False
            elif meta is not None:
                outcome, extra = ("undetected", {})
                if self.classify_injections:
                    s_first, s_cache, _, _ = attempt(ModelFault.none())
                    outcome, extra = self._shadow_outcome(
                        first, new_cache, (s_first, s_cache))
                self._record_injection(meta, "prefill_chunk", outcome,
                                       **extra)

            with tr.span("serve.chunk.wait", {"what": "tokens"}):
                first = np.asarray(first)
            with tr.span("serve.chunk.commit"):
                self.cache = new_cache
                self.keys = self.keys.at[jnp.asarray(slot_list)].set(
                    jnp.asarray(nkeys)[:A])
                self.stats.prefill_chunks += A
                now = time.perf_counter()
                for i, (slot, cur, take, fin) in enumerate(rows):
                    cur.filled += take
                    self.pos[slot] = cur.filled
                    if not fin:
                        continue
                    req = cur.req
                    req.generated.append(int(first[i]))
                    req.times.append(now)
                    self.stats.tokens += 1
                    self.stats.prompt_tokens_total += cur.total
                    self.stats.prefix_tokens_shared += cur.prefix
                    del self._prefill_cursors[slot]
                    if len(req.generated) >= req.max_new_tokens:
                        self._finish(req)          # budget met at prefill
                        self._release(slot)
                        continue
                    self.active[slot] = req
                    if self.index is not None:
                        self.index.add(req.prompt, self.pool.tables[slot])
        return True

    def _decode_core(self, fault: ModelFault | None = None) -> dict:
        """One decode step for all active slots (``step`` has run the
        paged growth guard).  Returns {uid: token}."""
        if not self.active:
            return {}
        tr = self._tr
        with tr.span("serve.decode", {"rows": len(self.active),
                                      "shape": f"{self.slots}x1"}):
            with tr.span("serve.decode.prepare"):
                toks = np.zeros((self.slots, 1), np.int32)
                mask = np.zeros((self.slots,), bool)
                for s, req in self.active.items():
                    toks[s, 0] = req.generated[-1]
                    mask[s] = True
                pos = jnp.asarray(self.pos)    # (slots,) vectorized cursor
                tables = (self.pool.device_tables()
                          if self.pool is not None else None)
                toks_dev, mask_dev = jnp.asarray(toks), jnp.asarray(mask)
                f = fault if fault is not None else ModelFault.none()
                meta = self._take_injection_meta("manual") \
                    if fault is not None else None
                # a sticky permanent fault models a faulty UNIT: it
                # corrupts the retry exactly like the attempt (retry
                # cannot clear it — the detect->recompute loop's
                # transient-fault assumption breaks, which is the
                # 2205.12177 detection gap this campaign mode
                # exercises); transient/manual faults retry clean as
                # before
                retry_f = f if (meta is not None
                                and meta.get("kind") == "permanent") \
                    else ModelFault.none()

            prev_cache = self.cache
            prev_keys = self.keys

            def attempt(fa):
                return self._decode(self.params, toks_dev, prev_cache, pos,
                                    mask_dev, prev_keys, tables, fa)

            with tr.span("serve.decode.dispatch"):
                nxt, new_cache, flag, nkeys = attempt(f)
            self.stats.steps += 1
            if self.pool is not None:
                # per-step occupancy samples: benchmarks report mean/
                # median/peak blocks_used (the paged capacity win)
                # without poking mid-run
                self.stats.observe_blocks_used(self.pool.blocks_used)
                self.stats.blocks_shared_peak = max(
                    self.stats.blocks_shared_peak, self.pool.blocks_shared)
            with tr.span("serve.decode.wait", {"what": "flag"}):
                faulted = bool(flag)
            if faulted:
                # ABFT detection -> recompute from pre-step state (clean
                # run, same per-slot keys: the retry resamples the same
                # token)
                self.stats.faults_detected += 1
                tr.instant("fault_detected", {"phase": "decode"})
                for _ in range(self.policy.max_retries):
                    self.stats.retries += 1
                    with tr.span("serve.retry", {"call": "decode"}):
                        with tr.span("serve.decode.dispatch"):
                            nxt, new_cache, flag, nkeys = attempt(retry_f)
                        with tr.span("serve.decode.wait",
                                     {"what": "flag"}):
                            faulted = bool(flag)
                    if not faulted:
                        break
                if meta is not None:
                    self._record_injection(
                        meta, "decode",
                        "uncorrected" if faulted else "corrected")
                if faulted:
                    self.stats.hard_faults += 1
                    tr.instant("hard_fault", {"phase": "decode"})
                    if not self.policy.evict_on_hard_fault:
                        raise RuntimeError("persistent fault after retry")
                    # the flag is step-global: every in-flight request may
                    # be corrupted, so evict them all with recorded errors
                    # and keep the engine alive for subsequent admissions
                    # (shared blocks survive as long as ANY sharer was
                    # admitted later with live references — refcounts
                    # gate the free list)
                    for s, req in list(self.active.items()):
                        self._finish(req, "hard_fault:decode", evict=True)
                        del self.active[s]
                        self._release(s)
                    return {}
            elif meta is not None:
                # UNDETECTED injection: shadow-stream comparison — re-run
                # the same call clean from the pre-step state and
                # compare.  The faulted result stays committed (realistic
                # propagation); only the classification consumes the
                # shadow.
                outcome, extra = ("undetected", {})
                if self.classify_injections:
                    s_nxt, s_cache, _, _ = attempt(ModelFault.none())
                    outcome, extra = self._shadow_outcome(
                        nxt, new_cache, (s_nxt, s_cache))
                self._record_injection(meta, "decode", outcome, **extra)

            with tr.span("serve.decode.wait", {"what": "tokens"}):
                nxt = np.asarray(nxt)
            with tr.span("serve.decode.commit"):
                self.cache = new_cache
                self.keys = nkeys
                out = {}
                finished = []
                now = time.perf_counter()
                for s, req in list(self.active.items()):
                    t = int(nxt[s])
                    req.generated.append(t)
                    req.times.append(now)
                    self.pos[s] += 1
                    out[req.uid] = t
                    self.stats.tokens += 1
                    if len(req.generated) >= req.max_new_tokens:
                        self._finish(req)
                        finished.append(s)
                for s in finished:
                    del self.active[s]
                    self._release(s)
                self._last_decode_tokens = len(out)
        return out

    def _serve_core(self, fault: ModelFault | None = None) -> dict:
        """Route one resident-slot step: the speculative verify core
        when a proposer is attached, else plain decode.  Leaves
        ``_last_decode_tokens`` holding the step's actual decode-side
        token count (window tokens for verify) for the intensity
        observation — with speculation on, a verify step scores K+1
        tokens per slot and the per-step scheme selection must see that
        multiplied intensity."""
        self._last_decode_tokens = 0
        if self.spec is not None:
            return self._verify_core(fault)
        return self._decode_core(fault)

    def _retune_draft_len(self) -> None:
        """Auto draft-length re-tuning as slot occupancy drifts: the
        roofline K depends on how many slots share the verify step
        (batch multiplies its token count), so the knob re-tunes from
        live occupancy exactly like the chunk budget.  While escalated,
        the adaptive policy's ``shrink_draft`` tightens it further."""
        k = max(1, self.plan.tune_draft_len(
            batch=max(1, len(self.active))))
        if self.adaptive is not None and self.protection_level \
                and self.adaptive.shrink_draft < 1.0:
            k = max(1, int(k * self.adaptive.shrink_draft))
        self.draft_len = k

    def _verify_core(self, fault: ModelFault | None = None) -> dict:
        """One speculative verify step for all active slots: propose up
        to ``draft_len`` tokens per slot (clamped so a window never
        overruns the slot's remaining token budget), score all K_s+1
        positions in ONE jitted ``verify`` call through the same
        ABFT-checked path as decode, then accept host-side — greedy:
        longest draft prefix matching the per-position argmax targets
        plus one bonus target (provably the unsped engine's stream,
        byte for byte); sampling: the rejection rule (exact in law).

        Fault handling is the chunk-retry machinery in verify flavor: a
        detected fault re-executes ONLY this draft window from the
        pre-step cache/keys — the per-slot cursors never moved, so
        rollback to the last accepted position is simply "don't
        advance" — and a sticky permanent exhausts the retry budget and
        evicts as decode does.  Returns {uid: last emitted token}."""
        tr = self._tr
        with tr.span("serve.verify", {"rows": len(self.active)}) as call:
            with tr.span("serve.verify.prepare"):
                if self.draft_auto:
                    self._retune_draft_len()
                proposals: dict = {}
                for s, req in sorted(self.active.items()):
                    budget = min(self.draft_len,
                                 req.max_new_tokens - len(req.generated) - 1)
                    d = (np.asarray(self.spec.propose(req, budget), np.int32)
                         if budget > 0 else np.zeros((0,), np.int32))
                    proposals[s] = d[:max(0, budget)]
                    self.stats.draft_proposed += len(proposals[s])
                # paged growth/COW guard over the WHOLE window (tables
                # frozen across the attempt/retry window, same as decode)
                self._copy_cow_blocks(self.scheduler.grow_for_verify(
                    {s: len(d) for s, d in proposals.items()}))
                if not self.active:
                    return {}
                T = self.draft_len + 1
                toks = np.zeros((self.slots, T), np.int32)
                mask = np.zeros((self.slots,), bool)
                valid = np.zeros((self.slots,), np.int32)
                for s, req in self.active.items():
                    d = proposals[s]
                    toks[s, 0] = req.generated[-1]
                    toks[s, 1:1 + len(d)] = d
                    mask[s] = True
                    valid[s] = len(d) + 1
                window_tokens = int(valid.sum())
                pos = jnp.asarray(self.pos)
                tables = (self.pool.device_tables()
                          if self.pool is not None else None)
                dev = (jnp.asarray(toks), jnp.asarray(mask),
                       jnp.asarray(valid))
                f = fault if fault is not None else ModelFault.none()
                meta = self._take_injection_meta("manual") \
                    if fault is not None else None
                retry_f = f if (meta is not None
                                and meta.get("kind") == "permanent") \
                    else ModelFault.none()
            call.set_args(shape=f"{self.slots}x{T}", tokens=window_tokens,
                          draft_len=self.draft_len)

            prev_cache = self.cache
            prev_keys = self.keys

            def attempt(fa):
                return self._verify(self.params, dev[0], prev_cache, pos,
                                    dev[1], dev[2], prev_keys, tables, fa)

            with tr.span("serve.verify.dispatch"):
                logits, new_cache, flag, nkeys = attempt(f)
            self.stats.steps += 1
            if self.pool is not None:
                self.stats.observe_blocks_used(self.pool.blocks_used)
                self.stats.blocks_shared_peak = max(
                    self.stats.blocks_shared_peak, self.pool.blocks_shared)
            with tr.span("serve.verify.wait", {"what": "flag"}):
                faulted = bool(flag)
            if faulted:
                self.stats.faults_detected += 1
                tr.instant("fault_detected", {"phase": "verify"})
                for _ in range(self.policy.max_retries):
                    self.stats.retries += 1
                    self.stats.verify_retries += 1
                    with tr.span("serve.retry", {"call": "verify"}):
                        with tr.span("serve.verify.dispatch"):
                            logits, new_cache, flag, nkeys = attempt(
                                retry_f)
                        with tr.span("serve.verify.wait",
                                     {"what": "flag"}):
                            faulted = bool(flag)
                    if not faulted:
                        break
                if meta is not None:
                    self._record_injection(
                        meta, "verify",
                        "uncorrected" if faulted else "corrected")
                if faulted:
                    self.stats.hard_faults += 1
                    tr.instant("hard_fault", {"phase": "verify"})
                    if not self.policy.evict_on_hard_fault:
                        raise RuntimeError("persistent fault after retry")
                    for s, req in list(self.active.items()):
                        self._finish(req, "hard_fault:verify", evict=True)
                        del self.active[s]
                        self._release(s)
                    return {}
            elif meta is not None:
                outcome, extra = ("undetected", {})
                if self.classify_injections:
                    s_logits, s_cache, _, _ = attempt(ModelFault.none())
                    outcome, extra = self._shadow_outcome(
                        logits, new_cache, (s_logits, s_cache))
                self._record_injection(meta, "verify", outcome, **extra)

            with tr.span("serve.verify.wait", {"what": "tokens"}):
                logits = np.asarray(logits)
            with tr.span("serve.verify.commit"):
                self.cache = new_cache
                self.keys = nkeys
                out = {}
                finished = []
                now = time.perf_counter()
                for s, req in list(self.active.items()):
                    d = proposals[s]
                    rows = logits[s, :len(d) + 1]
                    if self.temperature <= 0.0:
                        targets = np.argmax(rows, axis=-1).astype(np.int32)
                        emitted = greedy_accept(d, targets)
                    else:
                        emitted = rejection_sample(
                            d, target_probs(rows, self.temperature,
                                            self.top_k),
                            prev_keys[s])
                    self.stats.draft_accepted += len(emitted) - 1
                    for t in emitted:
                        req.generated.append(int(t))
                        req.times.append(now)
                        self.stats.tokens += 1
                    self.pos[s] += len(emitted)
                    out[req.uid] = int(emitted[-1])
                    if len(req.generated) >= req.max_new_tokens:
                        self._finish(req)
                        finished.append(s)
                for s in finished:
                    del self.active[s]
                    self._release(s)
                self._last_decode_tokens = window_tokens
        return out

    def run(self, requests: list, fault_at: tuple | None = None,
            admit_fault_at: tuple | None = None) -> dict:
        """Drive admission + decode to completion (continuous batching).

        ``fault_at``: (step_idx, ModelFault) decode-step injection —
        armed from that step index on, it fires at the first step that
        actually decodes (a step with no active slots re-arms the
        injection for the next real step instead of silently dropping
        it); ``admit_fault_at``: (uid, ModelFault) injected into the
        admission batch that contains that request uid (campaign hooks).
        Where an armed fault actually LANDED — the executed engine step
        and phase (decode / prefill_chunk / prefill), plus its detection
        outcome — is recorded in ``stats.injection_log`` (one entry per
        executed injection, ``source="fault_at"`` with the armed step
        index) instead of being consumed silently.

        Results are collected from the engine's finished-event queue —
        O(1) amortized per request — instead of rescanning every request
        each step (the seed's O(requests x steps) done-scan)."""
        pending = list(requests)
        results = {
            r.uid: r.generated for r in requests if r.done}  # pre-done edge
        self._drain_finished()
        step_i = 0
        step_fault_armed = fault_at is not None
        while pending or self.active or self._prefill_cursors:
            if pending and self.free_slots():
                if admit_fault_at is not None:
                    uid, afault = admit_fault_at
                    consumed = self.admit(pending, fault=afault,
                                          fault_uid=uid)
                    # consumed exactly once: only when the target actually
                    # went through prefill (not filtered out beforehand)
                    if any(r.uid == uid
                           and r.error not in PRE_PREFILL_ERRORS
                           and r.max_new_tokens > 0
                           for r in consumed):
                        admit_fault_at = None
                else:
                    self.admit(pending)
            fault = None
            if step_fault_armed and step_i >= fault_at[0]:
                fault = fault_at[1]
                # placement ground truth: the landing site records the
                # executed step + phase in stats.injection_log
                self._injection_meta = {
                    "source": "fault_at", "kind": "manual",
                    "armed_step": fault_at[0], "run_step": step_i}
            steps_before = self.stats.steps
            self.step(fault)
            if fault is not None and self.stats.steps > steps_before:
                step_fault_armed = False     # injection hit a real step
            step_i += 1
            for req in self._drain_finished():
                if req.uid not in results:
                    results[req.uid] = req.generated
        return results

    # ------------------------------------------------------------ stats
    def cache_stats(self) -> dict:
        """Cache geometry + occupancy, without poking at private pytrees.

        Common keys: ``kind``, ``slots``, ``max_len``, ``bytes_total``
        (allocated cache bytes across all layers), ``tokens_capacity``
        (cache entries the allocation can hold), ``active_tokens`` (sum
        of live cursors), ``utilization``, ``fragmentation``,
        ``blocks_shared``, and ``prefix_hit_rate``.

        Paged ``utilization`` divides live logical tokens by *allocated*
        tokens (``blocks_used * block_size``) — NOT total pool capacity,
        which hid internal fragmentation behind an always-small ratio.
        ``fragmentation`` is its complement: the allocated-but-unfilled
        share (partial last blocks).  Under prefix sharing, logical
        tokens can exceed allocated tokens (several slots count the same
        shared block), so utilization may exceed 1.0 — that excess IS the
        sharing win.  Paged engines also report ``block_size`` /
        ``blocks_total`` / ``blocks_used`` / ``blocks_free`` /
        ``tokens_allocated``."""
        stats = {
            "kind": self.cache_kind,
            "slots": self.slots,
            "max_len": self.max_len,
            "bytes_total": pytree_bytes(self.cache),
            "active_tokens": int(
                sum(int(self.pos[s]) for s in self.active)
                + sum(int(self.pos[s]) for s in self._prefill_cursors)),
        }
        if self.pool is not None:
            allocated = self.pool.blocks_used * self.pool.block_size
            stats.update(
                block_size=self.pool.block_size,
                blocks_total=self.pool.num_blocks,
                blocks_used=self.pool.blocks_used,
                blocks_free=self.pool.blocks_free,
                blocks_shared=self.pool.blocks_shared,
                tokens_capacity=self.pool.num_blocks
                * self.pool.block_size,
                tokens_allocated=allocated,
            )
        else:
            stats["tokens_capacity"] = self.slots * self.max_len
            stats["tokens_allocated"] = stats["tokens_capacity"]
            stats["blocks_shared"] = 0
        alloc = stats["tokens_allocated"]
        stats["utilization"] = stats["active_tokens"] / alloc if alloc else 0.0
        stats["fragmentation"] = (
            max(0.0, 1.0 - stats["utilization"]) if alloc else 0.0)
        stats["prefix_hit_rate"] = self.stats.prefix_hit_rate
        return stats
