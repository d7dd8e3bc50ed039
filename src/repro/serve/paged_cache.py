"""Paged KV-cache subsystem: block-table memory manager for the
continuous-batching engine.

Why paging (ISSUE 2 / ROADMAP "Paged KV cache"): the paper's deployment
scenario (§6) is latency-sensitive serving where decode is memory-bandwidth
bound, so KV-cache footprint directly gates batch size — and batch size is
what the intensity-guided selector's decode-side arithmetic-intensity
predictions key on.  With dense per-slot rows every request pays
``max_len`` memory; with a vLLM-style block pool, long and short requests
share a fixed set of fixed-size blocks and the sustainable slot count rises
to what the *actual* traffic needs.

Block-table layout
------------------
The device-side cache is a **pool**: per layer, the KV tensor's leading
``(slots, max_len)`` dims are replaced by ``(num_blocks, block_size)``:

    GQA:    k/v    (num_blocks, block_size, KV_heads, head_dim)
    MLA:    latent (num_blocks, block_size, kv_lora + rope)
    mamba:  conv/SSD state stays per-slot — it is O(1) per request (that
            is the whole point of SSMs), i.e. every slot owns exactly one
            implicit, permanently-resident block; no table indirection is
            needed or useful.

The host-side ``BlockPool`` owns the free list and one **block table per
slot** — a row of physical block ids, padded with an out-of-range
``SENTINEL`` (== num_blocks).  All layers share the SAME logical table;
each layer indexes its own physical pool with it (the vLLM layout).  A
token at logical position ``t`` of slot ``s`` lives at

    pool[ table[s, t // block_size], t % block_size ]

Device-side access is sentinel-safe by construction:

  * scatters use ``.at[...].set(mode='drop')`` — writes routed to the
    sentinel (padding tokens, inactive slots, freed rows) vanish;
  * gathers use ``take(mode='fill', fill_value=0)`` — sentinel blocks read
    as zeros and are masked by per-row lengths before the softmax, exactly
    like dense padding.

Prefix sharing, refcounts, and copy-on-write
--------------------------------------------
Templated traffic (system prompts, few-shot headers) makes many requests
open with the *same* tokens, and identical tokens at identical positions
produce identical KV — so their leading table entries can point at the
SAME physical blocks.  Three pieces make that safe:

  * **Refcounts.**  Every physical block carries a reference count: 1 when
    drawn from the free list, +1 per additional table entry that aliases
    it (``try_admit_prefix``), -1 when a referencing slot releases it.
    ``free_slot`` returns a block to the free list only when its LAST
    reference drops — evicting one sharer (hard fault, ``oom:kv_blocks``
    growth failure) can never free or scribble on blocks a live request
    still references.

  * **Content-hash index.**  ``PrefixIndex`` maps hash *chains* over fully
    cached blocks (key_i = H(key_{i-1}, tokens of block i)) to physical
    block ids, plus the partial tail block of each registered prompt.
    Lookups re-verify the stored tokens, so a hash collision degrades to
    "no match", never to silent cross-request corruption.  Entries are
    registered only after a prompt's prefill has been accepted (clean ABFT
    flag) and are purged the moment their block is physically freed.

  * **Copy-on-write.**  Blocks are immutable once shared *except* through
    COW: when a slot must write into a block another slot references —
    the last, partial block of a shared prefix, which the new request's
    suffix continues into — ``try_cow`` redirects the slot's table entry
    to a fresh block and the engine device-copies the payload before any
    jitted step runs.  Full shared blocks are never written again (decode
    cursors sit past the prompt), so sharing full blocks needs no copy.

Invariants (enforced by the property tests):

  * ``blocks_free + blocks_used == num_blocks`` at every point;
  * ``refcount[b] ==`` number of table entries naming ``b``; a block is
    on the free list iff its refcount is 0;
  * alloc -> share -> evict round trips in any order never double-free or
    leak a block.

Interaction with ABFT recovery snapshots
----------------------------------------
The engine's detect->recompute loop snapshots the *device* cache by simply
keeping the pre-step pytree alive (functional update).  That remains
sufficient under paging because the pool update is functional too — a
retry re-scatters into the held ``prev_cache`` pool.  The one new
invariant: the **host** block tables AND refcounts must not change between
a faulty attempt and its clean retry, so the engine performs all
allocation / sharing / COW (including the COW device copies, which are
plain data movement, not ABFT-protected GEMMs) strictly *before* the
jitted step and all frees / index registrations strictly *after* the flag
has been read back.  Hard-fault eviction then drops the victim slots'
references; blocks whose refcount reaches zero return to the free list
and their index entries are purged, while blocks a surviving sharer still
references stay resident (covered by the refcount lifecycle tests).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


class PoolExhausted(Exception):
    """Raised by the strict alloc API when the free list cannot cover a
    request.  The engine uses the non-throwing ``try_*`` variants and
    records an ``error`` on the request instead."""


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Number of blocks needed to hold ``n_tokens`` cache entries."""
    return max(0, -(-int(n_tokens) // block_size))


@dataclasses.dataclass
class BlockPool:
    """Host-side free-list allocator + per-slot block tables.

    ``num_blocks`` physical blocks of ``block_size`` tokens are shared by
    ``slots`` logical sequences.  ``table_width`` bounds the per-slot
    logical length at ``table_width * block_size`` tokens (the engine sets
    it to cover ``max_len``).  Freed blocks go to the head of the free
    list (LIFO) so reuse after eviction is immediate and testable.
    """

    num_blocks: int
    block_size: int
    slots: int
    table_width: int

    def __post_init__(self):
        assert self.num_blocks >= 1 and self.block_size >= 1
        self.sentinel = self.num_blocks
        self.reset()

    # ------------------------------------------------------------ queries
    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_used(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def blocks_shared(self) -> int:
        """Physical blocks currently referenced by more than one slot."""
        return int((self.refcount > 1).sum())

    def ref_of(self, block: int) -> int:
        return int(self.refcount[block])

    def slot_blocks(self, slot: int) -> int:
        return int(self._used[slot])

    def capacity_tokens(self, slot: int) -> int:
        """Tokens the slot's current allocation can hold."""
        return self.slot_blocks(slot) * self.block_size

    def can_admit(self, n_tokens: int) -> bool:
        return blocks_for(n_tokens, self.block_size) <= self.blocks_free

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        """Drop every allocation (fresh engine / full eviction)."""
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._used = np.zeros((self.slots,), np.int32)
        self.refcount = np.zeros((self.num_blocks,), np.int32)
        self.tables = np.full(
            (self.slots, self.table_width), self.num_blocks, np.int32)
        self.sentinel = self.num_blocks

    def try_alloc(self, slot: int, n_tokens: int) -> bool:
        """Allocate blocks so ``slot`` can hold ``n_tokens`` tokens
        (fresh sequence: the slot must currently own no blocks).  All-or-
        nothing: on exhaustion nothing is allocated and False returns."""
        assert self._used[slot] == 0, f"slot {slot} already allocated"
        return self.try_grow(slot, n_tokens)

    def alloc(self, slot: int, n_tokens: int) -> None:
        if not self.try_alloc(slot, n_tokens):
            raise PoolExhausted(
                f"need {blocks_for(n_tokens, self.block_size)} blocks, "
                f"{self.blocks_free} free")

    def try_grow(self, slot: int, n_tokens: int) -> bool:
        """Ensure ``slot`` can hold ``n_tokens`` tokens, allocating the
        delta (decode crossing a block boundary).  All-or-nothing."""
        need = blocks_for(n_tokens, self.block_size)
        have = int(self._used[slot])
        if need <= have:
            return True
        if need > self.table_width or need - have > len(self._free):
            return False
        for b in range(have, need):
            blk = self._free.pop()
            self.tables[slot, b] = blk
            self.refcount[blk] = 1
        self._used[slot] = need
        return True

    def grow(self, slot: int, n_tokens: int) -> None:
        if not self.try_grow(slot, n_tokens):
            raise PoolExhausted(
                f"slot {slot}: grow to {n_tokens} tokens failed "
                f"({self.blocks_free} blocks free)")

    def try_admit_prefix(self, slot: int, n_tokens: int,
                         shared_ids) -> bool:
        """Admission with a shared prefix: the slot's leading table
        entries alias the given physical blocks (refcount +1 each, NO
        free-list draw), the remaining ``blocks_for(n_tokens)`` blocks
        come fresh from the free list.  All-or-nothing: on exhaustion
        nothing is allocated or referenced and False returns."""
        assert self._used[slot] == 0, f"slot {slot} already allocated"
        need = blocks_for(n_tokens, self.block_size)
        k = len(shared_ids)
        assert k <= need, "shared prefix longer than the prompt"
        if need > self.table_width or need - k > len(self._free):
            return False
        for i, blk in enumerate(shared_ids):
            assert self.refcount[blk] >= 1, f"sharing a free block {blk}"
            self.tables[slot, i] = int(blk)
            self.refcount[blk] += 1
        for i in range(k, need):
            blk = self._free.pop()
            self.tables[slot, i] = blk
            self.refcount[blk] = 1
        self._used[slot] = need
        return True

    def try_cow(self, slot: int, idx: int):
        """Copy-on-write: if the slot's table entry ``idx`` aliases a
        block another slot also references, redirect it to a fresh block.
        Returns ``(src, dst)`` for the caller's device copy, ``None`` when
        the block is exclusively owned (no copy needed).  Raises
        ``PoolExhausted`` when a copy is needed but the free list is empty
        — callers budget the COW block into their all-or-nothing check."""
        assert 0 <= idx < int(self._used[slot])
        src = int(self.tables[slot, idx])
        if self.refcount[src] <= 1:
            return None
        if not self._free:
            raise PoolExhausted(f"COW for slot {slot} needs a free block")
        dst = self._free.pop()
        self.refcount[src] -= 1
        self.refcount[dst] = 1
        self.tables[slot, idx] = dst
        return src, dst

    def free_slot(self, slot: int) -> list:
        """Drop the slot's references; blocks whose refcount reaches zero
        return to the free list.  Returns the list of *physically freed*
        block ids (so callers can purge content-index entries).
        Idempotent (freeing an empty slot is a no-op)."""
        n = int(self._used[slot])
        freed = []
        for b in range(n - 1, -1, -1):
            blk = int(self.tables[slot, b])
            self.refcount[blk] -= 1
            assert self.refcount[blk] >= 0, f"double free of block {blk}"
            if self.refcount[blk] == 0:
                self._free.append(blk)
                freed.append(blk)
        self.tables[slot, :] = self.num_blocks
        self._used[slot] = 0
        return freed

    # ------------------------------------------------------------ invariants
    def check_invariants(self) -> None:
        """Assert the refcount/free-list bookkeeping is exactly consistent
        with the tables (used by the lifecycle property tests)."""
        assert len(self._free) == len(set(self._free)), "free-list dup"
        refs = np.zeros((self.num_blocks,), np.int32)
        for s in range(self.slots):
            for b in range(int(self._used[s])):
                refs[int(self.tables[s, b])] += 1
        assert (refs == self.refcount).all(), "refcount != table references"
        on_free = np.zeros((self.num_blocks,), bool)
        on_free[self._free] = True
        assert ((self.refcount == 0) == on_free).all(), (
            "a block is on the free list iff its refcount is 0")
        assert self.blocks_free + self.blocks_used == self.num_blocks

    # ------------------------------------------------------------ device view
    def device_tables(self, rows=None) -> jnp.ndarray:
        """Block tables as an int32 device array — all slots, or the given
        row indices (admission batches pass their slot ids)."""
        t = self.tables if rows is None else self.tables[np.asarray(rows)]
        return jnp.asarray(t, jnp.int32)


# ================================================================ prefix index

_ROOT = "prefix-index-root"


@dataclasses.dataclass
class PrefixMatch:
    """Result of a prefix lookup: the physical blocks the new slot should
    alias (full blocks, plus at most one partial tail that the caller must
    COW before writing its suffix into it) and the matched token count."""

    shared_ids: list
    match_len: int
    partial: bool          # last entry of shared_ids is a partial block

    @property
    def full_blocks(self) -> int:
        return len(self.shared_ids) - (1 if self.partial else 0)


class PrefixIndex:
    """Content-hash index over cached prompt blocks.

    Full blocks are keyed by a hash *chain*: ``key_i = hash((key_{i-1},
    tokens_i))`` where ``tokens_i`` is the i-th block's token tuple — so a
    block only matches behind the exact prefix that produced its KV.  Each
    chain node also carries the partial tail blocks registered under it
    (a prompt whose length is not a block multiple).  Every entry stores
    its token tuple and lookups re-verify it: a Python-hash collision
    degrades to a miss, never to silent sharing of wrong content.

    Entries are added only for prompts whose prefill passed the ABFT check
    and are purged when their physical block is freed (refcount zero), so
    the index never names a block whose payload is stale or recycled.
    """

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self._full: dict = {}       # chain key -> (block_id, tokens)
        self._partial: dict = {}    # chain key -> [(block_id, tokens), ...]
        self._by_block: dict = {}   # block_id -> set of (kind, key)

    def _note(self, block: int, kind: str, key) -> None:
        self._by_block.setdefault(int(block), set()).add((kind, key))

    @staticmethod
    def _chain(parent, tokens: tuple):
        return hash((parent, tokens))

    # ------------------------------------------------------------ register
    def add(self, prompt, table_row) -> None:
        """Register a fully prefilled prompt: one chain entry per full
        block, plus the partial tail (if any) under its prefix's key.
        Re-registering existing content is a no-op (first writer wins —
        later identical prompts were sharers and alias the same ids)."""
        toks = tuple(int(t) for t in prompt)
        bs = self.block_size
        key = _ROOT
        for i in range(len(toks) // bs):
            blk_toks = toks[i * bs:(i + 1) * bs]
            key = self._chain(key, blk_toks)
            if key not in self._full:
                blk = int(table_row[i])
                self._full[key] = (blk, blk_toks)
                self._note(blk, "full", key)
        rem = len(toks) % bs
        if rem:
            tail = toks[len(toks) - rem:]
            cand = self._partial.setdefault(key, [])
            if not any(t == tail for _, t in cand):
                blk = int(table_row[len(toks) // bs])
                cand.append((blk, tail))
                self._note(blk, "partial", key)

    # ------------------------------------------------------------ lookup
    def match(self, prompt) -> PrefixMatch:
        """Longest cached prefix of ``prompt``, capped at ``len(prompt) -
        1`` tokens so the suffix prefill always has at least one token to
        produce the first sampled logits from."""
        toks = tuple(int(t) for t in prompt)
        bs = self.block_size
        cap = len(toks) - 1
        ids, key, matched = [], _ROOT, 0
        while matched + bs <= cap:
            blk_toks = toks[matched:matched + bs]
            nxt = self._chain(key, blk_toks)
            ent = self._full.get(nxt)
            if ent is None or ent[1] != blk_toks:     # miss or hash clash
                break
            ids.append(ent[0])
            key = nxt
            matched += bs
        # partial tail: reuse the longest common lead of a cached block
        # under this chain — the caller COWs it before writing its suffix.
        best_blk, best_m = None, 0
        candidates = list(self._partial.get(key, []))
        if matched + bs <= len(toks):
            # a cached FULL block can seed a partial share too: the cap
            # above may have stopped the chain one token short of it
            # (prompt identical to a block-aligned cached prompt)
            ent = self._full.get(self._chain(key, toks[matched:matched + bs]))
            if ent is not None:
                candidates.append((ent[0], ent[1]))
        for blk, cand_toks in candidates:
            m = 0
            lim = min(len(cand_toks), cap - matched)
            while m < lim and cand_toks[m] == toks[matched + m]:
                m += 1
            if m > best_m:
                best_blk, best_m = blk, m
        if best_m > 0:
            ids.append(best_blk)
            return PrefixMatch(ids, matched + best_m, partial=True)
        return PrefixMatch(ids, matched, partial=False)

    # ------------------------------------------------------------ purge
    def purge(self, freed_blocks) -> None:
        """Remove every entry naming a physically freed block."""
        for blk in freed_blocks:
            for kind, key in self._by_block.pop(int(blk), ()):
                if kind == "full":
                    ent = self._full.get(key)
                    if ent is not None and ent[0] == int(blk):
                        del self._full[key]
                else:
                    cand = self._partial.get(key)
                    if cand is not None:
                        cand[:] = [c for c in cand if c[0] != int(blk)]
                        if not cand:
                            del self._partial[key]


# ================================================================ pytrees
# Paged cache initializers, mirroring attention.init_*_cache / mamba's
# init_mamba_cache but with the (slots, max_len) dims replaced by the
# (num_blocks, block_size) pool.  Kept here so the subsystem owns its
# memory layout end to end; models/model.py routes by cache kind.

def init_paged_gqa_cache(cfg: ModelConfig, num_blocks: int,
                         block_size: int, dtype) -> dict:
    from repro.models.attention import eff_counts

    hd = cfg.resolved_head_dim
    _, KVp = eff_counts(cfg)
    return {
        "k": jnp.zeros((num_blocks, block_size, KVp, hd), dtype),
        "v": jnp.zeros((num_blocks, block_size, KVp, hd), dtype),
    }


def init_paged_mla_cache(cfg: ModelConfig, num_blocks: int,
                         block_size: int, dtype) -> dict:
    return {
        "latent": jnp.zeros(
            (num_blocks, block_size,
             cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype),
    }


def init_paged_mamba_cache(cfg: ModelConfig, slots: int, dtype) -> dict:
    """Mamba state under paging == dense: constant-size per slot (one
    implicit resident block per slot; see module docstring)."""
    from repro.models.mamba import init_mamba_cache

    return init_mamba_cache(cfg, slots, dtype)


# ================================================================ device ops
# Sentinel-safe scatter/gather between logical (row, position) coordinates
# and the physical pool.  Shared by the GQA and MLA paged paths.

def paged_scatter_prefill(pool, layer, new, tables, lengths, starts=None):
    """Write an admission batch into entry ``layer`` of a stacked pool.

    pool: (R, NB, BS, ...); new: (A, L, ...) padded to a common L;
    tables: (A, W) int32 rows (sentinel-padded); lengths: (A,) valid
    token counts of ``new``.  Positions >= lengths[a] are routed to the
    sentinel and dropped.

    ``starts`` (A,) int32: logical position of each row's FIRST token —
    the prefix-sharing suffix prefill writes ``new[a, t]`` at logical
    position ``starts[a] + t`` (the shared prefix already lives in the
    pool).  ``None`` keeps the from-zero fast path bit-for-bit."""
    nb, bs = pool.shape[1], pool.shape[2]
    A, L = new.shape[0], new.shape[1]
    t = jnp.arange(L, dtype=jnp.int32)
    valid = t[None, :] < lengths[:, None]
    if starts is None:
        blk = jnp.take(tables, t // bs, axis=1)        # (A, L)
        off = jnp.broadcast_to(t % bs, (A, L))
    else:
        logical = starts[:, None].astype(jnp.int32) + t[None, :]
        blk = jnp.take_along_axis(tables, logical // bs, axis=1,
                                  mode="clip")
        off = logical % bs
    blk = jnp.where(valid, blk, nb)                    # force-drop padding
    return pool.at[layer, blk, off].set(new.astype(pool.dtype), mode="drop")


def paged_scatter_decode(pool, layer, new, tables, pos):
    """Write one new entry per slot at its own cursor, in entry ``layer``.

    pool: (R, NB, BS, ...); new: (B, ...); tables: (B, W); pos: (B,)
    int32.  Inactive/freed slots carry sentinel tables, so their writes
    drop — no activity mask is needed (the table IS the guard)."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(
        tables, (pos[:, None] // bs).astype(jnp.int32), axis=1)[:, 0]
    off = pos % bs
    return pool.at[layer, blk, off].set(new.astype(pool.dtype), mode="drop")


def paged_gather(pool, layer, tables):
    """Materialize per-slot contiguous KV from entry ``layer`` of a
    stacked pool.

    pool: (R, NB, BS, ...); tables: (B, W) -> (B, W*BS, ...).  Sentinel
    blocks read as zeros; callers mask by per-row length before softmax.
    (The Pallas paged flash_decode skips this materialization and indexes
    the pool directly via the block table — this is the XLA reference
    path.)"""
    B, W = tables.shape
    g = pool.at[layer, tables].get(mode="fill", fill_value=0)
    return g.reshape((B, W * pool.shape[2]) + pool.shape[3:])


def pytree_bytes(tree) -> int:
    """Total bytes of every array leaf (cache_stats accounting)."""
    import jax

    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype")
    )
