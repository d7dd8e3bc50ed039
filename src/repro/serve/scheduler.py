"""Scheduler layer of the serving engine (executor-hierarchy refactor).

Host-side request/slot/block bookkeeping, split out of the old
``ServeEngine`` monolith:

  * the ``Request`` / ``ChunkCursor`` lifecycle records and the
    ``EngineStats`` counters;
  * the fixed-capacity slot table with its per-slot position cursors;
  * admission screening — budget/length checks, paged block allocation,
    prefix-index matching + COW planning, and the bounded head-of-line
    lookahead — as one pure-host pass (``select_admission``) that never
    touches the model;
  * the chunked-prefill cursor queue (``park_prefill`` /
    ``plan_chunks``);
  * the paged decode-step growth guard (``grow_for_decode``): claim the
    next block / COW a shared block BEFORE the jitted step so tables are
    stable across the attempt/retry window, evicting slots that cannot
    grow.

Everything here is host state, mutated strictly outside the jitted
attempt/retry window — the same discipline the block tables always had.
Device work (jitted entry points, sharded params/cache) lives in
``serve/runner.py`` and ``serve/executor.py``; the ``ServeEngine``
facade (serve/engine.py) orchestrates the three layers and carries the
retry policy across them.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.serve.paged_cache import BlockPool, PrefixIndex, blocks_for


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (L,) int32
    max_new_tokens: int           # budget of generated tokens (incl. the
                                  # prefill-sampled first token)
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: str | None = None      # set when evicted (hard fault, too long,
                                  # block-pool exhaustion)
    # wall-clock perf_counter() stamp per generated token (benchmarks
    # derive TTFT / inter-token-latency percentiles from these)
    times: list = dataclasses.field(default_factory=list, repr=False)


@dataclasses.dataclass
class ChunkCursor:
    """Resumable prefill state of one admitted-but-not-yet-decoding
    request under the chunked-prefill scheduler: ``prompt[:filled]`` is
    resident in the cache (including any shared prefix), the rest still
    has to be prefilled in token-budgeted chunks.  Host-only state —
    mutated strictly outside the jitted attempt/retry window, like the
    block tables."""

    req: Request
    total: int                    # len(prompt)
    filled: int                   # logical tokens already resident
    prefix: int                   # shared-prefix tokens (stats accounting)


# errors set before a request ever reaches prefill (admission screening)
PRE_PREFILL_ERRORS = ("prompt_too_long", "oom:block_pool")


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """ABFT detect->recompute policy (see serve/engine.py docstring)."""

    max_retries: int = 1           # clean re-executions after a detection
    evict_on_hard_fault: bool = True   # evict + record error vs raise


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    tokens: int = 0
    faults_detected: int = 0
    retries: int = 0
    hard_faults: int = 0
    evictions: int = 0         # resident requests that lost their slot
    rejections: int = 0        # screened out before prefill (never resident)
    # prefix sharing
    prompt_tokens_total: int = 0
    prefix_tokens_shared: int = 0
    cow_copies: int = 0
    # chunked prefill
    prefill_chunks: int = 0    # prompt-chunks executed (one per row per step)
    chunk_retries: int = 0     # clean re-executions of a faulted chunk only
    chunk_budget_retunes: int = 0  # auto-budget changes as occupancy drifts
    mixed_steps: int = 0       # steps carrying decode AND prefill tokens
    decode_only_steps: int = 0
    prefill_only_steps: int = 0
    # speculative decoding
    draft_proposed: int = 0    # draft tokens offered to verify steps
    draft_accepted: int = 0    # draft tokens the verify step accepted
    verify_retries: int = 0    # clean re-executions of a faulted verify
    #                            window only (subset of ``retries``)
    # per-step intensity-guided selection trace: one entry per executed
    # step, {"step", "decode", "prefill", "intensity", "scheme"} — the
    # serving-time record of the paper's §5.3 decision re-made from each
    # step's ACTUAL token composition.  Bounded by the same deterministic
    # stride decimation as the occupancy samples.
    selection_trace: list = dataclasses.field(default_factory=list)
    selection_count: int = 0
    selection_stride: int = 1
    # steps whose intensity-guided selection differs from the previous
    # step's (the regime crossings telemetry emits as instant events)
    scheme_flips: int = 0
    # fault-campaign classification (shadow-stream harness): every
    # injected fault — campaign OR hand-armed — is classified by outcome.
    # faults_injected = corrected + uncorrected + sdc + masked once the
    # step resolves; sdc (silent data corruption: undetected AND the
    # shadow clean re-execution disagrees) is the number the protection
    # stack exists to hold at zero.
    faults_injected: int = 0
    faults_corrected: int = 0      # detected, retry re-executed clean
    faults_uncorrected: int = 0    # detected, persisted through retries
    sdc_faults: int = 0            # undetected, outputs provably corrupt
    masked_faults: int = 0         # undetected, outputs provably clean
    # adaptive protection (ErrorAdaptivePolicy) level changes
    protection_escalations: int = 0
    protection_deescalations: int = 0
    # ground truth on injection placement: one entry per injected fault,
    # {"engine_step", "phase", "source", "kind", "layer", "site", "row",
    #  "col", "bit", "outcome"} — what run()'s fault_at disarm used to
    # consume silently.  Bounded like the occupancy samples.
    injection_log: list = dataclasses.field(default_factory=list)
    injections_dropped: int = 0    # log entries lost to the bound
    # per-step pool occupancy aggregates (one observation per executed
    # decode step on a paged engine).  The mean is exact (sum/count); the
    # median comes from a BOUNDED sample list kept small by deterministic
    # stride decimation, so a long-lived serving engine never accumulates
    # unbounded per-step state
    blocks_used_sum: int = 0
    blocks_used_count: int = 0
    blocks_used_samples: list = dataclasses.field(default_factory=list)
    blocks_used_stride: int = 1
    blocks_used_peak: int = 0
    blocks_shared_peak: int = 0

    MAX_OCCUPANCY_SAMPLES = 4096

    def observe_blocks_used(self, used: int) -> None:
        self.blocks_used_sum += used
        self.blocks_used_count += 1
        self.blocks_used_peak = max(self.blocks_used_peak, used)
        if self.blocks_used_count % self.blocks_used_stride == 0:
            self.blocks_used_samples.append(used)
            if len(self.blocks_used_samples) > self.MAX_OCCUPANCY_SAMPLES:
                # halve the sampling rate.  Keep the ODD indices: entry k
                # was recorded at observation (k+1)*stride, so [1::2]
                # retains exactly the even multiples of the old stride —
                # the multiples of the DOUBLED stride — and the
                # "entry k <=> observation (k+1)*stride" alignment
                # survives every decimation round ([::2] kept the odd
                # multiples, which the new stride can never produce)
                self.blocks_used_samples = self.blocks_used_samples[1::2]
                self.blocks_used_stride *= 2

    def observe_selection(self, decode: int, prefill: int,
                          intensity: float, scheme: str) -> None:
        """Record one step's (composition, intensity, scheme) decision."""
        if decode and prefill:
            self.mixed_steps += 1
        elif prefill:
            self.prefill_only_steps += 1
        else:
            self.decode_only_steps += 1
        self.selection_count += 1
        if self.selection_count % self.selection_stride == 0:
            self.selection_trace.append({
                "step": self.steps, "decode": decode, "prefill": prefill,
                "intensity": intensity, "scheme": scheme,
            })
            if len(self.selection_trace) > self.MAX_OCCUPANCY_SAMPLES:
                # decimation keeps the ODD indices (see
                # observe_blocks_used): trace[k] stays the observation
                # numbered (k+1)*selection_stride after ANY number of
                # rounds, so downstream consumers can reconstruct true
                # observation indices from (k, stride) alone
                self.selection_trace = self.selection_trace[1::2]
                self.selection_stride *= 2

    _OUTCOME_COUNTER = {
        "corrected": "faults_corrected",
        "uncorrected": "faults_uncorrected",
        "sdc": "sdc_faults",
        "masked": "masked_faults",
    }

    def record_injection(self, entry: dict) -> None:
        """Classify one injected fault (see ``injection_log``).  The
        outcome counters are the telemetry-facing aggregate; the log is
        the per-fault ground truth campaigns replay-check against."""
        self.faults_injected += 1
        attr = self._OUTCOME_COUNTER.get(entry.get("outcome"))
        if attr is not None:
            setattr(self, attr, getattr(self, attr) + 1)
        if len(self.injection_log) < self.MAX_OCCUPANCY_SAMPLES:
            self.injection_log.append(entry)
        else:
            self.injections_dropped += 1

    @property
    def blocks_used_mean(self) -> float:
        return self.blocks_used_sum / max(self.blocks_used_count, 1)

    @property
    def blocks_used_median(self) -> float:
        """Steady-state resident blocks: the median is robust to the
        cold-start wave, whose requests cannot share (nothing is cached
        yet) and briefly hold unshared copies of a common template."""
        s = sorted(self.blocks_used_samples)
        n = len(s)
        if not n:
            return 0.0
        return (s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2)

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_tokens_shared / max(self.prompt_tokens_total, 1)


def _pad_len(n: int) -> int:
    """Bucket prefill lengths to multiples of 8 to bound jit recompiles."""
    return max(8, -(-n // 8) * 8)


def _pad_rows(n: int, cap: int) -> int:
    """Bucket a prefill batch's ROW count to the next power of two (capped
    at the engine's slot count).  Chunk batches vary in both row count and
    chunk length step to step; bucketing both dims bounds the number of
    jitted ``_prefill_chunk`` variants at O(log2(slots) x chunk/8) for an
    entire run instead of one compile per composition."""
    r = 1
    while r < n:
        r *= 2
    return min(r, cap)


@dataclasses.dataclass
class AdmissionBatch:
    """Result of one host-side admission screening pass: the requests
    that will prefill this round (with their assigned slots, prefix
    plans, and pending COW payload moves) plus everything consumed from
    the pending queue (admitted OR finished/rejected during
    screening)."""

    admitted: list
    slot_list: list
    prefix_plans: list
    cow_pairs: list
    consumed: list


class Scheduler:
    """Host-side slot/block/request bookkeeping (see module docstring).

    The ``stats`` and ``tracer`` attributes are deliberately mutable:
    the engine facade rebinds them on warm-up resets and telemetry
    attachment and keeps its own references in sync."""

    def __init__(self, *, slots: int, max_len: int, admit_lookahead: int,
                 stats: EngineStats, tracer,
                 pool: BlockPool | None = None,
                 index: PrefixIndex | None = None):
        self.slots = slots
        self.max_len = max_len
        self.admit_lookahead = int(admit_lookahead)
        self.stats = stats
        self.tracer = tracer
        self.pool = pool
        self.index = index
        self.pos = np.zeros((slots,), np.int32)      # per-slot write cursor
        self.active: dict = {}                        # slot -> Request
        self.prefill_cursors: dict = {}      # slot -> ChunkCursor (FIFO)
        # requests that turned done inside admit()/step(), awaiting run()'s
        # result collection (replaces the O(requests x steps) done-scan)
        self.done_events: list = []
        # head-of-line state: (uid of the deferred head, bypasses spent)
        self.hol_uid: int | None = None
        self.hol_bypassed = 0

    # ------------------------------------------------------------- slots
    def free_slots(self) -> list:
        return [s for s in range(self.slots)
                if s not in self.active and s not in self.prefill_cursors]

    def release(self, slot: int) -> None:
        """Drop a slot's cache references (paged: refcount decrements;
        blocks whose last reference dropped return to the free list and
        their prefix-index entries are purged)."""
        if self.pool is not None:
            freed = self.pool.free_slot(slot)
            if self.index is not None and freed:
                self.index.purge(freed)
        self.pos[slot] = 0

    def finish(self, req: Request, error: str | None = None, *,
               reject: bool = False, evict: bool = False) -> None:
        """Mark a request done and queue it for run()'s result collection.
        ``reject``: screened out before prefill (never held cache state);
        ``evict``: a resident request lost its slot."""
        if error is not None:
            req.error = error
        req.done = True
        if reject:
            self.stats.rejections += 1
            self.tracer.instant("reject", {"uid": req.uid, "error": error})
        if evict:
            self.stats.evictions += 1
            self.tracer.instant("evict", {"uid": req.uid, "error": error})
        self.done_events.append(req)

    def drain_finished(self) -> list:
        done, self.done_events = self.done_events, []
        return done

    # --------------------------------------------------------- admission
    def select_admission(self, pending: list) -> AdmissionBatch:
        """One admission screening pass over ``pending`` (consumed
        requests are removed IN PLACE): budget/length checks, paged block
        claims, prefix matching + COW planning, bounded head-of-line
        lookahead.  Pure host work — the returned batch still has to be
        prefilled (or parked as chunk cursors) by the engine."""
        free = self.free_slots()
        batch = AdmissionBatch([], [], [], [], [])
        if not pending or not free:
            return batch
        admitted, slot_list = batch.admitted, batch.slot_list
        consumed, consumed_idx = batch.consumed, []
        head_deferred = False
        scanned_past_head = 0
        for i, req in enumerate(pending):
            if len(slot_list) >= len(free):
                break
            if head_deferred:
                # bounded lookahead: examine at most admit_lookahead
                # requests past the deferred head
                if scanned_past_head >= self.admit_lookahead:
                    break
                scanned_past_head += 1
            if req.max_new_tokens <= 0:
                self.finish(req)             # zero budget: nothing to do
                consumed.append(req)
                consumed_idx.append(i)
                continue
            # the prompt plus the decode budget must fit in the cache rows
            if len(req.prompt) + max(req.max_new_tokens - 1, 0) > \
                    self.max_len:
                self.finish(req, "prompt_too_long", reject=True)
                consumed.append(req)
                consumed_idx.append(i)
                continue
            slot = free[len(slot_list)]
            plan = None
            if self.pool is not None:
                # paged admission: blocks for the prompt are claimed up
                # front (decode growth is on-demand).  A request that can
                # NEVER fit is rejected with a recorded error; a request
                # that merely hit transient pressure (blocks held by
                # in-flight requests) is DEFERRED until decode frees
                # blocks.  No livelock: deferral with an empty engine is
                # impossible (a full free list that still cannot cover
                # the prompt means never-fits), so something is always
                # decoding and eventually freeing.
                need = blocks_for(len(req.prompt), self.pool.block_size)
                if need > self.pool.num_blocks or \
                        need > self.pool.table_width:
                    self.finish(req, "oom:block_pool", reject=True)
                    consumed.append(req)
                    consumed_idx.append(i)
                    continue
                if self.index is not None:
                    plan = self.index.match(req.prompt)
                    if not plan.shared_ids:
                        plan = None
                # a shared full block costs no free-list draw; the COW
                # copy of a partial tail does (need counts its index)
                fresh = need - (plan.full_blocks if plan else 0)
                if fresh > self.pool.blocks_free:
                    if not head_deferred:
                        head_deferred = True
                        if self.hol_uid != req.uid:
                            self.hol_uid = req.uid
                            self.hol_bypassed = 0
                    continue                 # deferred, keep scanning
                if head_deferred:
                    # admitting past the deferred head spends its bypass
                    # budget; once exhausted admission is strict FIFO and
                    # every freed block is reserved for the head
                    if self.hol_bypassed >= self.admit_lookahead:
                        break
                    self.hol_bypassed += 1
                if plan is not None:
                    ok = self.pool.try_admit_prefix(
                        slot, len(req.prompt), plan.shared_ids)
                else:
                    ok = self.pool.try_alloc(slot, len(req.prompt))
                assert ok, "alloc failed after fresh <= blocks_free check"
                if plan is not None and plan.partial:
                    # the suffix will write into the shared partial tail:
                    # copy-on-write it now, before any jitted step
                    pair = self.pool.try_cow(
                        slot, len(plan.shared_ids) - 1)
                    assert pair is not None, "partial tail was unshared"
                    batch.cow_pairs.append(pair)
            admitted.append(req)
            slot_list.append(slot)
            batch.prefix_plans.append(plan)
            consumed.append(req)
            consumed_idx.append(i)
        for i in reversed(consumed_idx):
            pending.pop(i)
        if self.hol_uid is not None and any(
                r.uid == self.hol_uid for r in consumed):
            self.hol_uid, self.hol_bypassed = None, 0      # head unblocked
        return batch

    def park_prefill(self, batch: AdmissionBatch) -> None:
        """Chunked-prefill admission: the allocated requests become chunk
        cursors (NO model call) and their cursors start past any shared
        prefix; step() co-schedules the chunks against resident decodes."""
        for slot, req, plan in zip(batch.slot_list, batch.admitted,
                                   batch.prefix_plans):
            start = plan.match_len if plan is not None else 0
            self.prefill_cursors[slot] = ChunkCursor(
                req=req, total=len(req.prompt), filled=start,
                prefix=start)
            self.pos[slot] = start

    def plan_chunks(self, budget: int) -> list:
        """Pick this step's prefill chunks: cursors in admission (FIFO)
        order, each taking ``min(budget left, tokens left)``.  Returns
        [(slot, cursor, take, final)]."""
        rows = []
        for slot, cur in self.prefill_cursors.items():
            if budget <= 0:
                break
            take = min(budget, cur.total - cur.filled)
            rows.append((slot, cur, take, cur.filled + take == cur.total))
            budget -= take
        return rows

    # ------------------------------------------------------------ decode
    def grow_for_decode(self) -> list:
        """Paged decode-step guard: claim the block each cursor is about
        to enter BEFORE the jitted step (tables must be stable across the
        attempt/retry window) and COW any block another slot still
        references; a slot that cannot grow is evicted with a recorded
        error, freeing blocks for the rest.  Returns the COW (src, dst)
        pairs whose payload the engine must copy on device.  A decode
        step is exactly a zero-draft verify window."""
        return self.grow_for_verify({})

    def grow_for_verify(self, window: dict) -> list:
        """Paged verify-step guard: ``window[slot]`` is the slot's draft
        length K_s, so the step writes K_s + 1 rows at
        cursor..cursor+K_s (K_s = 0, the default, is a plain decode
        step).  Claims blocks through the window's LAST write and COWs
        EVERY shared block the window touches — the whole window must be
        writable before the jitted attempt because tables stay frozen
        across the attempt/retry window.  Admission COWs the shared
        partial tail eagerly, so the COW guard only fires on exotic
        lifecycles — but scribbling on a sharer's block is silent
        corruption, so it is unconditional.  A slot that cannot grow is
        evicted with a recorded error, freeing blocks for the rest.
        Returns the COW (src, dst) pairs whose payload the engine must
        copy on device."""
        cow_pairs: list = []
        if self.pool is None:
            return cow_pairs
        for s in sorted(self.active):
            k_s = int(window.get(s, 0))
            first = int(self.pos[s]) // self.pool.block_size
            last = (int(self.pos[s]) + k_s) // self.pool.block_size
            last = min(last, self.pool.slot_blocks(s) - 1)
            evicted = False
            for idx in range(first, last + 1):
                if self.pool.refcount[self.pool.tables[s, idx]] > 1:
                    if self.pool.blocks_free == 0:
                        req = self.active.pop(s)
                        self.finish(req, "oom:kv_blocks", evict=True)
                        self.release(s)
                        evicted = True
                        break
                    cow_pairs.append(self.pool.try_cow(s, idx))
            if evicted:
                continue
            if not self.pool.try_grow(s, int(self.pos[s]) + k_s + 1):
                req = self.active.pop(s)
                self.finish(req, "oom:kv_blocks", evict=True)
                self.release(s)
        return cow_pairs
