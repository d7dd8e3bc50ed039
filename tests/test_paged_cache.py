"""Paged KV-cache subsystem tests: BlockPool accounting, sentinel-safe
device scatter/gather, the block-table-indexed fused-ABFT decode kernel,
and end-to-end paged-vs-dense engine equivalence (greedy decode is
deterministic, so any paging bug shows up as a token divergence).

Block sizes in the equivalence tests divide ``max_len`` so the paged
attention shapes equal the dense ones — token streams must then match
EXACTLY, with and without injected faults."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, FaultSpec, Scheme
from repro.models import ModelFault, build_model
from repro.models.layers import decode_attention
from repro.serve.engine import RecoveryPolicy, Request, ServeEngine
from repro.serve.paged_cache import (
    BlockPool,
    PoolExhausted,
    blocks_for,
    paged_gather,
    paged_scatter_decode,
    paged_scatter_prefill,
    pytree_bytes,
)

ABFT = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, model, params


@pytest.fixture(scope="module")
def mla_model():
    """deepseek-style MLA: the paged latent pool path."""
    cfg = scaled_down(get_config("deepseek-v3-671b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2), dtype=jnp.float32)
    return cfg, model, params


@pytest.fixture(scope="module")
def hybrid_model():
    """jamba: mamba + attention interleave — covers the per-slot SSM
    state riding alongside the paged attention pool."""
    cfg = scaled_down(get_config("jamba-v0.1-52b"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1), dtype=jnp.float32)
    return cfg, model, params


def _engine(model, params, slots=2, max_len=64, **kw):
    return ServeEngine(model, params, slots=slots, max_len=max_len,
                       abft=ABFT, dtype=jnp.float32, **kw)


def _req(uid, length, n=5):
    return Request(uid=uid,
                   prompt=np.arange(1, 1 + length, dtype=np.int32),
                   max_new_tokens=n)


# ================================================================ BlockPool

def test_pool_alloc_free_accounting():
    bp = BlockPool(num_blocks=8, block_size=4, slots=3, table_width=4)
    assert bp.blocks_free == 8 and bp.blocks_used == 0
    assert bp.try_alloc(0, 9)            # 3 blocks
    assert bp.slot_blocks(0) == 3 and bp.capacity_tokens(0) == 12
    assert bp.try_alloc(1, 4)            # 1 block
    assert bp.blocks_used == 4
    # grow within the already-covered capacity is a no-op
    assert bp.try_grow(0, 12) and bp.slot_blocks(0) == 3
    assert bp.try_grow(0, 13) and bp.slot_blocks(0) == 4
    assert len(bp.free_slot(0)) == 4     # unshared: all physically freed
    assert bp.blocks_used == 1 and bp.blocks_free == 7
    assert bp.free_slot(0) == []         # idempotent
    bp.reset()
    assert bp.blocks_used == 0 and (bp.tables == bp.sentinel).all()


def test_pool_exhaustion_is_all_or_nothing():
    bp = BlockPool(num_blocks=3, block_size=4, slots=2, table_width=4)
    assert bp.try_alloc(0, 8)            # 2 of 3 blocks
    before = bp.tables.copy()
    assert not bp.try_alloc(1, 9)        # needs 3, only 1 free
    assert bp.blocks_used == 2           # nothing leaked
    np.testing.assert_array_equal(bp.tables, before)
    with pytest.raises(PoolExhausted):
        bp.alloc(1, 9)
    # table width also bounds growth (logical max_len)
    assert not bp.try_grow(0, 17)        # 5 blocks > width 4


def test_pool_free_list_reuse_after_eviction():
    """Freed blocks go back to the head of the free list: an evicted
    request's blocks are the next ones handed out."""
    bp = BlockPool(num_blocks=6, block_size=4, slots=3, table_width=3)
    assert bp.try_alloc(0, 12)
    victim_blocks = list(bp.tables[0, :3])
    assert bp.try_alloc(1, 4)
    bp.free_slot(0)                      # eviction
    assert bp.try_alloc(2, 12)
    assert list(bp.tables[2, :3]) == victim_blocks   # immediate reuse
    assert blocks_for(0, 4) == 0 and blocks_for(5, 4) == 2


# ================================================================ device ops

def test_scatter_gather_roundtrip_and_sentinel_drop():
    bp = BlockPool(num_blocks=5, block_size=4, slots=2, table_width=3)
    pool = jnp.zeros((1, 5, 4, 2), jnp.float32)      # one layer's entry
    lens = np.array([6, 3], np.int32)
    for s in range(2):
        assert bp.try_alloc(s, int(lens[s]))
    new = jnp.arange(2 * 8 * 2, dtype=jnp.float32).reshape(2, 8, 2) + 1.0
    pool = paged_scatter_prefill(
        pool, 0, new, bp.device_tables(), jnp.asarray(lens))
    g = paged_gather(pool, 0, bp.device_tables())   # (2, 12, 2)
    for s in range(2):
        np.testing.assert_array_equal(
            np.asarray(g[s, : lens[s]]), np.asarray(new[s, : lens[s]]))
        # beyond the valid length everything reads as zero (dropped
        # padding writes, sentinel fill)
        assert not np.asarray(g[s, lens[s]:]).any()

    # decode scatter: slot 0 appends at pos 6; a freed slot's write drops
    bp.free_slot(1)
    step = jnp.full((2, 2), 7.0)
    pool2 = paged_scatter_decode(
        pool, 0, step, bp.device_tables(), jnp.asarray([6, 3], jnp.int32))
    g2 = paged_gather(pool2, 0, bp.device_tables())
    np.testing.assert_array_equal(np.asarray(g2[0, 6]), [7.0, 7.0])
    assert float(jnp.sum(pool2)) == pytest.approx(
        float(jnp.sum(pool)) + 14.0)    # only slot 0's write landed


def test_paged_flash_decode_matches_reference():
    from repro.kernels.flash_ops import flash_decode_paged

    rng = np.random.default_rng(0)
    B, H, KV, D, BS, W, NB = 3, 4, 2, 16, 8, 4, 9
    bp = BlockPool(NB, BS, B, W)
    lens = np.array([5, 17, 24], np.int32)
    for s in range(B):
        assert bp.try_alloc(s, int(lens[s]))
    k_new = jnp.asarray(rng.standard_normal((B, 24, KV, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, 24, KV, D)), jnp.float32)
    tables = bp.device_tables()
    pool_k = paged_scatter_prefill(
        jnp.zeros((1, NB, BS, KV, D), jnp.float32), 0, k_new, tables,
        jnp.asarray(lens))
    pool_v = paged_scatter_prefill(
        jnp.zeros((1, NB, BS, KV, D), jnp.float32), 0, v_new, tables,
        jnp.asarray(lens))
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)

    ref = decode_attention(
        q, paged_gather(pool_k, 0, tables), paged_gather(pool_v, 0, tables),
        jnp.asarray(lens))
    out, chk = flash_decode_paged(q, pool_k[0], pool_v[0], tables,
                                  jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not bool(chk.flag)            # clean run: no ABFT detection


def test_paged_flash_decode_check_ignores_alien_blocks():
    """Sentinel table tails are clamped onto real (alien) blocks and
    reused blocks keep stale KV; the ABFT score check must be blind to
    them — otherwise their magnitudes inflate the detection threshold
    and real faults in short sequences slip through."""
    from repro.kernels.flash_ops import flash_decode_paged

    rng = np.random.default_rng(1)
    B, H, KV, D, BS, W, NB = 1, 2, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((NB, BS, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB, BS, KV, D)), jnp.float32)
    # slot owns only block 0, length 5; table tail is sentinel (=NB),
    # which the wrapper clamps onto block NB-1
    tables = jnp.asarray([[0, NB, NB, NB]], jnp.int32)
    lens = jnp.asarray([5], jnp.int32)
    # blow up the alien block the clamp lands on
    k_hot = k.at[NB - 1].set(1e6)
    v_hot = v.at[NB - 1].set(1e6)

    out, chk = flash_decode_paged(q, k, v, tables, lens)
    out_hot, chk_hot = flash_decode_paged(q, k_hot, v_hot, tables, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_hot))
    # the detection threshold must not widen because of alien data
    np.testing.assert_allclose(np.asarray(chk.threshold),
                               np.asarray(chk_hot.threshold), rtol=1e-6)
    assert not bool(chk_hot.flag)


# ================================================================ engine

def _mixed_reqs(n=5):
    return [_req(0, 5, n), _req(1, 11, n), _req(2, 23, n)]


def test_paged_engine_matches_dense_mixed_lengths(small_model):
    _, model, params = small_model
    dense = _engine(model, params).run(_mixed_reqs())
    paged_eng = _engine(model, params, cache_kind="paged", block_size=16)
    paged = paged_eng.run(_mixed_reqs())
    assert dense == paged
    # all blocks returned once traffic drains
    assert paged_eng.pool.blocks_used == 0
    assert paged_eng.stats.hard_faults == 0


def test_paged_engine_matches_dense_under_fault_recovery(small_model):
    """A decode-step fault is detected and recovered by recompute from the
    held pre-step pool; streams still match dense exactly."""
    _, model, params = small_model
    fault = ModelFault.at(1, "mlp_down", FaultSpec.value(0, 2, 1e4))
    dense = _engine(model, params).run(_mixed_reqs(6), fault_at=(2, fault))
    eng = _engine(model, params, cache_kind="paged", block_size=16)
    paged = eng.run(_mixed_reqs(6), fault_at=(2, fault))
    assert eng.stats.faults_detected >= 1 and eng.stats.retries >= 1
    assert eng.stats.hard_faults == 0
    assert dense == paged


def test_paged_admission_fault_retries_from_pre_admission_pool(small_model):
    _, model, params = small_model
    fault = ModelFault.at(1, "mlp_down", FaultSpec.value(0, 2, 1e4))
    dense = _engine(model, params).run(
        [_req(0, 5, 4)], admit_fault_at=(0, fault))
    eng = _engine(model, params, cache_kind="paged", block_size=8,
                  policy=RecoveryPolicy(max_retries=1))
    paged = eng.run([_req(0, 5, 4)], admit_fault_at=(0, fault))
    assert eng.stats.faults_detected == 1 and eng.stats.hard_faults == 0
    assert dense == paged


def test_hard_fault_eviction_frees_blocks_for_reuse(small_model):
    """Persistent decode fault: the victim's blocks return to the free
    list and the NEXT request is served out of the reused blocks."""
    _, model, params = small_model
    eng = _engine(model, params, slots=1, cache_kind="paged", block_size=8,
                  policy=RecoveryPolicy(max_retries=0))
    victim, later = _req(0, 5, 6), _req(1, 8, 3)
    fault = ModelFault.at(1, "mlp_down", FaultSpec.value(0, 2, 1e4))
    results = eng.run([victim, later], fault_at=(1, fault))
    assert victim.error == "hard_fault:decode"
    assert eng.stats.hard_faults == 1
    assert eng.pool.blocks_used == 0     # everything came back
    assert results[1] == _engine(model, params, slots=1).run(
        [_req(1, 8, 3)])[1]


def test_pool_exhaustion_rejects_admission_with_error(small_model):
    """A request that can NEVER fit the pool is rejected with a recorded
    error (no crash, no livelock) and the rest of the traffic is
    served."""
    _, model, params = small_model
    eng = _engine(model, params, cache_kind="paged", block_size=16,
                  num_blocks=2)           # 32 cache tokens total
    big, small = _req(0, 40, 3), _req(1, 9, 3)
    results = eng.run([big, small])
    assert big.error == "oom:block_pool" and big.generated == []
    # pre-prefill screening is a REJECTION, not an eviction: the request
    # never held a slot or cache state (accounting-split satellite)
    assert eng.stats.rejections >= 1
    assert eng.stats.evictions == 0
    assert results[1] == _engine(model, params).run([_req(1, 9, 3)])[1]


def test_transient_pool_pressure_defers_instead_of_rejecting(small_model):
    """A request that fits the pool but not RIGHT NOW (blocks held by
    in-flight requests) is deferred, not rejected: it completes without
    error once decode frees blocks, matching the dense engine."""
    _, model, params = small_model
    # 3 blocks of 16: req 0 holds 2, req 1 needs 2 -> deferred until
    # req 0 finishes, then served out of the freed blocks
    eng = _engine(model, params, cache_kind="paged", block_size=16,
                  num_blocks=3)
    a, b = _req(0, 30, 3), _req(1, 20, 3)
    results = eng.run([a, b])
    assert a.error is None and b.error is None
    assert len(results[0]) == 3 and len(results[1]) == 3
    dense = _engine(model, params).run([_req(0, 30, 3), _req(1, 20, 3)])
    assert results == dense
    assert eng.pool.blocks_used == 0


def test_pool_exhaustion_mid_decode_evicts_with_error(small_model):
    """Growth across a block boundary can also exhaust the pool: the slot
    that cannot grow is evicted with a recorded error; the engine and the
    remaining slot keep serving."""
    _, model, params = small_model
    # 3 blocks of 8: two 8-token prompts fill 2 blocks; the single spare
    # goes to slot 0 at its first boundary crossing, slot 1 then starves
    eng = _engine(model, params, cache_kind="paged", block_size=8,
                  num_blocks=3)
    a, b = _req(0, 8, 6), _req(1, 8, 6)
    eng.run([a, b])
    assert {a.error, b.error} == {None, "oom:kv_blocks"}
    ok = a if a.error is None else b
    assert len(ok.generated) == 6
    assert eng.pool.blocks_used == 0


def test_paged_mla_latent_matches_dense(mla_model):
    """deepseek MLA: the paged latent pool (kv_lora + rope dims) must
    reproduce the dense streams for mixed-length traffic."""
    _, model, params = mla_model
    def reqs():
        return [_req(0, 5, 4), _req(1, 14, 4)]
    dense = _engine(model, params, max_len=32).run(reqs())
    paged = _engine(model, params, max_len=32, cache_kind="paged",
                    block_size=8).run(reqs())
    assert dense == paged


def test_paged_hybrid_ssm_attention_matches_dense(hybrid_model):
    """jamba: the paged pool carries the attention layers while mamba
    conv/SSD state stays per-slot — streams must still match dense."""
    _, model, params = hybrid_model
    def reqs():
        return [_req(0, 4, 4), _req(1, 13, 4)]
    dense = _engine(model, params, max_len=32).run(reqs())
    paged = _engine(model, params, max_len=32, cache_kind="paged",
                    block_size=8).run(reqs())
    assert dense == paged


def test_cache_stats_reports_paged_savings(small_model):
    """The acceptance metric: a working-set-sized pool allocates fewer
    cache bytes than slots x max_len while serving identical streams."""
    _, model, params = small_model
    dense_eng = _engine(model, params, slots=4)
    paged_eng = _engine(model, params, slots=4, cache_kind="paged",
                        block_size=16, num_blocks=4)  # 64 of 256 tokens
    d, p = dense_eng.cache_stats(), paged_eng.cache_stats()
    assert d["kind"] == "dense" and p["kind"] == "paged"
    assert p["bytes_total"] == d["bytes_total"] // 4
    assert p["tokens_capacity"] == 64 and d["tokens_capacity"] == 256
    # skewed traffic: one long, three short — fits in 4 blocks
    def reqs():
        return [_req(0, 30, 3), _req(1, 4, 3), _req(2, 5, 3)]
    assert dense_eng.run(reqs()) == paged_eng.run(reqs())
    assert p["bytes_total"] == pytree_bytes(paged_eng.cache)
    # mid-run occupancy was visible through the pool, all freed at drain
    assert paged_eng.pool.blocks_used == 0
    assert paged_eng.stats.tokens == 9


# ================================================================ sampling

def test_sampling_default_greedy_unchanged(small_model):
    """temperature=0 (default) must reproduce the greedy streams bit for
    bit — the sampler satellite may not disturb existing behavior."""
    _, model, params = small_model
    base = _engine(model, params).run(_mixed_reqs(4))
    with_seed = _engine(model, params, seed=123).run(_mixed_reqs(4))
    assert base == with_seed


def test_sampling_per_slot_keys_reproducible(small_model):
    _, model, params = small_model
    kw = dict(temperature=1.3, top_k=8, seed=11)
    r1 = _engine(model, params, **kw).run(_mixed_reqs(4))
    r2 = _engine(model, params, **kw).run(_mixed_reqs(4))
    assert r1 == r2                      # same per-slot key streams
    r3 = _engine(model, params, temperature=1.3, top_k=8, seed=12).run(
        _mixed_reqs(4))
    assert r1 != r3                      # seed actually reaches sampling
    # paged engine consumes the identical per-slot key sequence
    r4 = _engine(model, params, cache_kind="paged", block_size=16,
                 **kw).run(_mixed_reqs(4))
    assert r1 == r4
    for toks in r1.values():
        assert all(0 <= t < 256 for t in toks)


def test_sampling_top_k_larger_than_vocab_is_no_cutoff(small_model):
    """An oversized --top-k means "no cutoff", never a crash inside the
    jitted step (vocab here is 256)."""
    _, model, params = small_model
    r = _engine(model, params, temperature=1.0, top_k=10_000,
                seed=3).run([_req(0, 6, 3)])
    assert len(r[0]) == 3 and all(0 <= t < 256 for t in r[0])


def test_sampling_keys_independent_of_other_slot_activity(small_model):
    """A slot's key stream advances only on its OWN accepted steps: a
    request admitted into a slot that sat idle while another slot decoded
    samples exactly what it would have sampled admitted immediately."""
    _, model, params = small_model
    kw = dict(temperature=1.3, top_k=8, seed=11)

    late = _engine(model, params, **kw)
    assert len(late.admit([_req(0, 6, 8)])) == 1   # slot 0 decodes...
    for _ in range(3):
        late.step()                             # ...slot 1 sits idle
    a_late = _req(1, 9, 4)
    assert len(late.admit([a_late])) == 1       # lands on slot 1
    while late.active:
        late.step()

    early = _engine(model, params, **kw)
    a_early = _req(1, 9, 4)
    assert len(early.admit([_req(0, 6, 8), a_early])) == 2
    while early.active:
        early.step()

    assert a_late.generated == a_early.generated
