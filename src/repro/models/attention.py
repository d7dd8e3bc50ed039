"""Attention sublayers: GQA (llama/qwen/stablelm/jamba/vlm), absorbed MLA
(deepseek-v3), and cross-attention (whisper decoder / vlm image layers).

All projections run through the ABFT-protected dense().  Cache paths
take a layer's STACKED cache leaves (R, ...) and its index ``layer`` into
them (models/model.py carries the stack through the layer scan): they
write only the rows they produce and read their own entry where it is
consumed (functional style).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.markers import coverage_scope
from repro.configs.base import ModelConfig
from repro.models.layers import (
    LayerCtx,
    apply_rope,
    cache_layer,
    cache_write,
    chunked_attention,
    decode_attention,
    dense,
    or_flags,
    rms_norm,
    rope_tables,
    verify_attention,
)

F32 = jnp.float32


def _init(key, shape, scale=0.02, dtype=jnp.bfloat16):
    return (scale * jax.random.normal(key, shape, F32)).astype(dtype)


# ================================================================ GQA

def eff_counts(cfg: ModelConfig) -> tuple:
    """(H_eff, KV_eff): head counts after TP padding (DESIGN/§Perf).
    Padding preserves the kv-major (kv, group) head layout so the padded
    model is mathematically identical to the logical one (padded wo rows
    are zero)."""
    H, KV = cfg.n_heads, cfg.n_kv_heads
    Hp = max(cfg.pad_heads_to, H)
    KVp = max(cfg.pad_kv_heads_to, KV)
    G = H // max(KV, 1)
    Gp = Hp // max(KVp, 1)
    assert KVp * Gp == Hp and Gp >= G, (
        f"invalid head padding H={H}->{Hp}, KV={KV}->{KVp}")
    return Hp, KVp


def _pad_heads_in(w, d, KV, G, hd, KVp, Gp):
    """(d, KV*G*hd) -> (d, KVp*Gp*hd), zero-padding in kv-major layout."""
    if KV == KVp and G == Gp:
        return w
    w4 = w.reshape(d, KV, G, hd)
    w4 = jnp.pad(w4, ((0, 0), (0, KVp - KV), (0, Gp - G), (0, 0)))
    return w4.reshape(d, KVp * Gp * hd)


def _pad_heads_out(w, KV, G, hd, d, KVp, Gp):
    """(KV*G*hd, d) -> (KVp*Gp*hd, d) with ZERO rows for padded heads —
    padded-head attention garbage never reaches the residual stream."""
    if KV == KVp and G == Gp:
        return w
    w4 = w.reshape(KV, G, hd, d)
    w4 = jnp.pad(w4, ((0, KVp - KV), (0, Gp - G), (0, 0), (0, 0)))
    return w4.reshape(KVp * Gp * hd, d)


def _pad_bias(b, KV, G, hd, KVp, Gp):
    if KV == KVp and G == Gp:
        return b
    b3 = b.reshape(KV, G, hd)
    b3 = jnp.pad(b3, ((0, KVp - KV), (0, Gp - G), (0, 0)))
    return b3.reshape(KVp * Gp * hd)


def init_gqa(cfg: ModelConfig, key, dtype) -> dict:
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    Hp, KVp = eff_counts(cfg)
    G, Gp = H // KV, Hp // KVp
    ks = jax.random.split(key, 5)
    p = {
        "wq": _pad_heads_in(
            _init(ks[0], (cfg.d_model, H * hd), dtype=dtype),
            cfg.d_model, KV, G, hd, KVp, Gp),
        "wk": _pad_heads_in(
            _init(ks[1], (cfg.d_model, KV * hd), dtype=dtype),
            cfg.d_model, KV, 1, hd, KVp, 1),
        "wv": _pad_heads_in(
            _init(ks[2], (cfg.d_model, KV * hd), dtype=dtype),
            cfg.d_model, KV, 1, hd, KVp, 1),
        "wo": _pad_heads_out(
            _init(ks[3], (H * hd, cfg.d_model), dtype=dtype),
            KV, G, hd, cfg.d_model, KVp, Gp),
    }
    if cfg.qkv_bias:
        p["bq"] = _pad_bias(jnp.zeros((H * hd,), dtype), KV, G, hd, KVp, Gp)
        p["bk"] = _pad_bias(jnp.zeros((KV * hd,), dtype), KV, 1, hd, KVp, 1)
        p["bv"] = _pad_bias(jnp.zeros((KV * hd,), dtype), KV, 1, hd, KVp, 1)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(x, p, cfg: ModelConfig, ctx: LayerCtx, positions):
    B, L, _ = x.shape
    hd = cfg.resolved_head_dim
    Hp, KVp = eff_counts(cfg)
    q, f1 = dense(x, p["wq"], ctx, "qkv", b=p.get("bq"), tag="attn.q")
    k, f2 = dense(x, p["wk"], ctx, "qkv", b=p.get("bk"), tag="attn.k")
    v, f3 = dense(x, p["wv"], ctx, "qkv", b=p.get("bv"), tag="attn.v")
    q = q.reshape(B, L, Hp, hd)
    k = k.reshape(B, L, KVp, hd)
    v = v.reshape(B, L, KVp, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        cos, sin, rot = rope_tables(
            positions, hd, cfg.rope_theta, cfg.rope_pct)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)
    return q, k, v, or_flags(f1, f2, f3)


def _attend_full(q, k, v, ctx: LayerCtx, causal: bool):
    """Full-sequence attention core: fused-ABFT flash kernel when the
    policy enables it (protects the attention GEMMs themselves), else the
    XLA chunked path (GEMM projections still ABFT-protected)."""
    if ctx.abft.flash_attention:
        from repro.kernels.flash_ops import flash_attention

        out, chk = flash_attention(q, k, v, causal=causal)
        return out, chk.flag
    return chunked_attention(q, k, v, causal=causal), jnp.zeros((), bool)


def gqa_forward(x, p, cfg: ModelConfig, ctx: LayerCtx, positions,
                causal: bool = True):
    """Full-sequence attention (train / encoder).  x: (B, L, D)."""
    B, L, _ = x.shape
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    out, f_attn = _attend_full(q, k, v, ctx, causal)
    out = out.reshape(B, L, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, or_flags(flag, f_attn, f)


def _write_rows(leaf, layer, rows, starts, new, counts):
    """Write ``new[a, :counts[a]]`` (A, T, ...) into entry ``layer`` of the
    stacked cache leaf (R, B, S, ...), row ``rows[a]``, at positions
    ``starts[a]``, ``starts[a] + 1``, ...; positions at or past S drop.
    Rows past ``counts[a]`` (padding) are never written, so a bucketed
    pad near S cannot clamp backwards onto rows already written, as a
    ``dynamic_update_slice`` of the whole of ``new[a]`` would (the
    chunked-prefill resume path: earlier chunks occupy ``[0, starts[a])``).

    Each row reads a window of at least 8 positions of its entry, selects
    its new values in and writes the window back in place.  The window
    takes the layout of the loop-carried leaf, so the device keeps the
    cache in the layout it stores it in (on TPU v5e a 64-wide head puts
    positions minor, unpadded).  One scatter of the new rows instead
    wants the cache positions-major: even with the carry pinned
    (keep_storage_layout) the compiler then relayouts the whole carried
    cache into the scatter's layout and back in every layer, four
    cache-sized copies a layer."""
    S = leaf.shape[2]
    T = new.shape[1]
    W = min(S, max(T, 8))
    j = jnp.arange(W, dtype=jnp.int32)
    zeros = (0,) * (leaf.ndim - 3)
    new = new.astype(leaf.dtype)
    for a in range(new.shape[0]):
        start = starts[a].astype(jnp.int32)
        lo = jnp.clip(start, 0, S - W)
        src = j + (lo - start)                     # window slot -> new row
        put = (src >= 0) & (src < counts[a])
        at = (layer, rows[a], lo) + zeros
        win = jax.lax.dynamic_slice(leaf, at, (1, 1, W) + leaf.shape[3:])
        vals = jnp.take(new[a], jnp.clip(src, 0, T - 1), axis=0)
        put = put.reshape((1, 1, W) + (1,) * len(zeros))
        leaf = jax.lax.dynamic_update_slice(
            leaf, jnp.where(put, vals[None, None], win), at)
    return leaf


def _row_scatter(leaf, layer, new, pos):
    """Per-row KV write: ``new[b, 0]`` lands in entry ``layer`` of the
    stacked leaf (R, B, S, ...) at row ``b``, position ``pos[b]``
    (vectorized decode cursor; clamped into the row like a
    ``dynamic_update_slice``)."""
    B, S = new.shape[0], leaf.shape[2]
    return _write_rows(leaf, layer, jnp.arange(B, dtype=jnp.int32),
                       jnp.clip(pos, 0, S - 1), new,
                       jnp.ones((B,), jnp.int32))


def _slot_prefill_write(leaf, layer, new, slots):
    """Write ``new`` (A, L, ...) into rows ``slots`` of entry ``layer`` of
    the stacked engine cache (R, B_engine, S_max, ...) at positions
    [0, L)."""
    A, L = new.shape[0], new.shape[1]
    return _write_rows(leaf, layer, slots, jnp.zeros((A,), jnp.int32), new,
                       jnp.full((A,), L, jnp.int32))


def _vec_positions(pos, B):
    """Normalize a decode cursor to a (B,) vector of positions."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))


def gqa_prefill(x, p, cfg: ModelConfig, ctx: LayerCtx, positions, cache,
                layer, slots=None, lengths=None, starts=None):
    """Prefill: run full attention AND fill the cache.  cache: dict with
    stacked 'k','v' of shape (R, B, S_max, KV, hd); this layer owns entry
    ``layer`` and writes only the rows it produces there.

    ``slots``/``lengths`` (continuous-batching path): x is the admission
    batch (A, L, D) padded to a common L; k/v rows are scattered into the
    engine cache rows ``slots`` and attention is masked per-row at
    ``lengths`` so ragged prompts never attend into padding.

    ``starts`` (A,) int32 selects the RESUMABLE-CHUNK path (the dense-cache
    mirror of the paged suffix prefill): x holds one mid-prompt chunk per
    row, whose logical positions begin at ``starts[a]`` (``positions``
    already carries the offset, so rotary embeddings match the monolithic
    prefill bit for bit).  The chunk's k/v scatter in behind the already-
    resident prefix and attention runs over the slot's cache rows (prefix
    + fresh chunk) with a per-row causal ``q_offset`` and total-length key
    masking — byte-identical streams to the unchunked engine are the
    correctness contract."""
    B, L, _ = x.shape
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    if starts is None:
        out = chunked_attention(q, k, v, causal=True, lengths=lengths)
        if slots is None:
            new_cache = {"k": cache_write(cache["k"], layer, k),
                         "v": cache_write(cache["v"], layer, v)}
        else:
            new_cache = {
                "k": _slot_prefill_write(cache["k"], layer, k, slots),
                "v": _slot_prefill_write(cache["v"], layer, v, slots),
            }
    else:
        assert slots is not None, "chunked prefill needs slot targets"
        new_cache = {
            "k": _write_rows(cache["k"], layer, slots, starts, k, lengths),
            "v": _write_rows(cache["v"], layer, slots, starts, v, lengths),
        }
        out = chunked_attention(
            q, jnp.take(cache_layer(new_cache["k"], layer), slots, axis=0),
            jnp.take(cache_layer(new_cache["v"], layer), slots, axis=0),
            causal=True, q_offset=starts, lengths=starts + lengths)
    out = out.reshape(B, L, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, new_cache, or_flags(flag, f)


def gqa_decode(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache, layer):
    """One-token decode.  x: (B, 1, D); pos: scalar or (B,) per-slot
    position vector; cache k/v: stacked (R, B, S_max, KV, hd), this
    layer's entry ``layer``.  Each row writes its new k/v at its own
    cursor and attends its own valid prefix."""
    B = x.shape[0]
    pos = _vec_positions(pos, B)
    positions = pos[:, None]
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    ck = _row_scatter(cache["k"], layer, k, pos)
    cv = _row_scatter(cache["v"], layer, v, pos)
    lk, lv = cache_layer(ck, layer), cache_layer(cv, layer)
    if ctx.abft.flash_attention:
        from repro.kernels.flash_ops import flash_decode

        out, chk = flash_decode(q, lk, lv, pos + 1)
        f_attn = chk.flag
    else:
        out = decode_attention(q, lk, lv, pos + 1)
        f_attn = jnp.zeros((), bool)
    out = out.reshape(B, 1, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, {"k": ck, "v": cv}, or_flags(flag, f_attn, f)


def _window_scatter(leaf, layer, new, pos, valid):
    """Write ``new`` (B, T, ...) into entry ``layer`` of the stacked leaf
    (R, B, S, ...) at rows ``pos[b] .. pos[b] + T - 1``, keeping only the
    first ``valid[b]`` rows.  Out-of-window rows are never written — a
    ``dynamic_update_slice`` of the whole window would clamp a
    near-budget window backwards onto committed keys (the padded verify
    T is uniform across slots; per-slot draft budgets are not)."""
    return _write_rows(leaf, layer, jnp.arange(new.shape[0], dtype=jnp.int32),
                       pos, new, valid)


def gqa_verify(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache, layer,
               valid):
    """Speculative verify: x (B, T, D) holds each row's last committed
    token followed by its draft window; row b writes its first
    ``valid[b]`` k/v rows at positions ``pos[b]..`` of entry ``layer``
    and every query attends its own causal prefix (verify_attention).
    Rows beyond ``valid`` are shape ballast (uniform T across slots) —
    their writes drop and their logits are discarded host-side."""
    B, T, _ = x.shape
    pos = _vec_positions(pos, B)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    ck = _window_scatter(cache["k"], layer, k, pos, valid)
    cv = _window_scatter(cache["v"], layer, v, pos, valid)
    out = verify_attention(q, cache_layer(ck, layer), cache_layer(cv, layer),
                           pos + 1)
    out = out.reshape(B, T, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, {"k": ck, "v": cv}, or_flags(flag, f)


# ---------------------------------------------------------------- paged GQA

def gqa_paged_prefill(x, p, cfg: ModelConfig, ctx: LayerCtx, positions,
                      cache, layer, tables, lengths, starts=None):
    """Paged prefill: same ragged attention as the dense continuous-
    batching path (prompts attend only themselves), but k/v scatter into
    entry ``layer`` of the stacked block pool at
    ``tables[a, t // block_size]`` instead of dense engine rows.  cache
    k/v: (R, NB, BS, KV, hd); tables: (A, W).

    ``starts`` (A,) int32 selects the prefix-sharing SUFFIX path: x holds
    only each row's unshared suffix, whose logical positions begin at
    ``starts[a]`` (``positions`` already carries the offset, so rotary
    embeddings are computed from the true logical position — getting this
    wrong is silent corruption, which is why the shared-vs-unshared
    equivalence tests demand byte-identical streams).  The suffix k/v are
    scattered behind the resident prefix, then attention runs over the
    slot's GATHERED logical KV (prefix blocks + fresh suffix) with a
    per-row causal offset and total-length key masking."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_prefill

    B, L, _ = x.shape
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    if starts is None:
        out = chunked_attention(q, k, v, causal=True, lengths=lengths)
        new_cache = {
            "k": paged_scatter_prefill(cache["k"], layer, k, tables, lengths),
            "v": paged_scatter_prefill(cache["v"], layer, v, tables, lengths),
        }
    else:
        new_cache = {
            "k": paged_scatter_prefill(cache["k"], layer, k, tables, lengths,
                                       starts=starts),
            "v": paged_scatter_prefill(cache["v"], layer, v, tables, lengths,
                                       starts=starts),
        }
        out = chunked_attention(
            q, paged_gather(new_cache["k"], layer, tables),
            paged_gather(new_cache["v"], layer, tables),
            causal=True, q_offset=starts, lengths=starts + lengths)
    out = out.reshape(B, L, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, new_cache, or_flags(flag, f)


def gqa_paged_decode(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache,
                     layer, tables):
    """Paged one-token decode: scatter the new k/v entry at
    ``tables[b, pos[b] // block_size]`` of pool entry ``layer``, then
    attend the slot's own prefix — via the block-table-indexed Pallas
    flash kernel when the policy enables it, else gather + length-masked
    reference attention."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_decode

    B = x.shape[0]
    pos = _vec_positions(pos, B)
    positions = pos[:, None]
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    ck = paged_scatter_decode(cache["k"], layer, k[:, 0], tables, pos)
    cv = paged_scatter_decode(cache["v"], layer, v[:, 0], tables, pos)
    if ctx.abft.flash_attention:
        from repro.kernels.flash_ops import flash_decode_paged

        out, chk = flash_decode_paged(
            q, cache_layer(ck, layer), cache_layer(cv, layer), tables,
            pos + 1)
        f_attn = chk.flag
    else:
        out = decode_attention(
            q, paged_gather(ck, layer, tables),
            paged_gather(cv, layer, tables), pos + 1)
        f_attn = jnp.zeros((), bool)
    out = out.reshape(B, 1, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, {"k": ck, "v": cv}, or_flags(flag, f_attn, f)


def gqa_paged_verify(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache,
                     layer, valid, tables):
    """Paged speculative verify: the draft window's k/v scatter behind
    the committed prefix via the block tables (the prefix-sharing suffix
    scatter generalizes — per-row starts at the cursor, padding routed
    to the sentinel), then each query attends the gathered logical KV
    with its own per-query length mask."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_prefill

    B, T, _ = x.shape
    pos = _vec_positions(pos, B)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k, v, flag = _qkv(x, p, cfg, ctx, positions)
    ck = paged_scatter_prefill(cache["k"], layer, k, tables, valid, starts=pos)
    cv = paged_scatter_prefill(cache["v"], layer, v, tables, valid, starts=pos)
    out = verify_attention(
        q, paged_gather(ck, layer, tables),
        paged_gather(cv, layer, tables), pos + 1)
    out = out.reshape(B, T, -1)
    out, f = dense(out, p["wo"], ctx, "attn_out", tag="attn.o")
    return out, {"k": ck, "v": cv}, or_flags(flag, f)


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    _, KVp = eff_counts(cfg)
    return {
        "k": jnp.zeros((batch, max_len, KVp, hd), dtype),
        "v": jnp.zeros((batch, max_len, KVp, hd), dtype),
    }


# ================================================================ cross-attn

def init_cross(cfg: ModelConfig, key, dtype, kv_dim: int | None = None):
    hd = cfg.resolved_head_dim
    kv_dim = kv_dim or cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (cfg.d_model, cfg.n_heads * hd), dtype=dtype),
        "wk": _init(ks[1], (kv_dim, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": _init(ks[2], (kv_dim, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": _init(ks[3], (cfg.n_heads * hd, cfg.d_model), dtype=dtype),
    }


def cross_kv(mem, p, cfg: ModelConfig, ctx: LayerCtx):
    """Project encoder/vision memory to K/V once (reused every decode)."""
    B, S, _ = mem.shape
    hd = cfg.resolved_head_dim
    k, f1 = dense(mem, p["wk"], ctx, "cross_qkv", tag="cross.k")
    v, f2 = dense(mem, p["wv"], ctx, "cross_qkv", tag="cross.v")
    return (
        k.reshape(B, S, cfg.n_kv_heads, hd),
        v.reshape(B, S, cfg.n_kv_heads, hd),
        or_flags(f1, f2),
    )


def cross_forward(x, k, v, p, cfg: ModelConfig, ctx: LayerCtx):
    """Cross-attention: queries from x, K/V precomputed from memory."""
    B, L, _ = x.shape
    hd = cfg.resolved_head_dim
    q, f1 = dense(x, p["wq"], ctx, "cross_qkv", tag="cross.q")
    q = q.reshape(B, L, cfg.n_heads, hd)
    out = chunked_attention(q, k, v, causal=False)
    out = out.reshape(B, L, -1)
    out, f2 = dense(out, p["wo"], ctx, "cross_out", tag="cross.o")
    return out, or_flags(f1, f2)


# ================================================================ MLA
# Absorbed formulation (DESIGN.md §4): attention becomes MQA with one
# shared latent key space  k' = [c_kv ; k_pe]  (dim kv_lora + rope),
# v' = c_kv, per-head query  q' = [q_nope @ W_uk ; q_pe].

def init_mla(cfg: ModelConfig, key, dtype) -> dict:
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": _init(ks[0], (cfg.d_model, cfg.q_lora_rank), dtype=dtype),
        "q_a_norm": jnp.ones((cfg.q_lora_rank,), dtype),
        "wq_b": _init(ks[1], (cfg.q_lora_rank, H * (dn + dr)), dtype=dtype),
        "wkv_a": _init(
            ks[2], (cfg.d_model, cfg.kv_lora_rank + dr), dtype=dtype),
        "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        # up-projections, stored head-major for the absorbed form
        "w_uk": _init(ks[3], (H, dn, cfg.kv_lora_rank), dtype=dtype),
        "w_uv": _init(ks[4], (H, cfg.kv_lora_rank, dv), dtype=dtype),
        "wo": _init(ks[5], (H * dv, cfg.d_model), dtype=dtype),
    }


def _mla_q(x, p, cfg: ModelConfig, ctx: LayerCtx, positions):
    """Absorbed queries: (B, L, H, kv_lora + rope)."""
    B, L, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    qa, f1 = dense(x, p["wq_a"], ctx, "q_a", tag="mla.q_a")
    qa = rms_norm(qa, p["q_a_norm"], cfg.norm_eps)
    q, f2 = dense(qa, p["wq_b"], ctx, "qkv", tag="mla.q_b")
    q = q.reshape(B, L, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    cos, sin, rot = rope_tables(positions, dr, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin, rot)
    # absorb W_uk:  (B,L,H,dn) @ (H,dn,c) -> (B,L,H,c).  A weight-bearing
    # einsum outside the matmul-ABFT surface: flops[mla] marks it for the
    # auditor as a known gap (no fused MLA ABFT kernel yet).
    with coverage_scope("mla"):
        q_abs = jnp.einsum(
            "blhd,hdc->blhc", q_nope.astype(F32), p["w_uk"].astype(F32),
            preferred_element_type=F32).astype(x.dtype)
    q_full = jnp.concatenate([q_abs, q_pe], axis=-1)
    # scale uses the *pre-absorption* head dim (dn + dr)
    scale = (dn + dr) ** -0.5
    return q_full, scale, or_flags(f1, f2)


def _mla_latent_kv(x, p, cfg: ModelConfig, ctx: LayerCtx, positions):
    """Latent K/V: c_kv (B, L, c) + roped k_pe (B, L, dr)."""
    dr = cfg.qk_rope_head_dim
    kv, f = dense(x, p["wkv_a"], ctx, "kv_a", tag="mla.kv_a")
    c_kv, k_pe = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    cos, sin, rot = rope_tables(positions, dr, cfg.rope_theta)
    k_pe = apply_rope(k_pe[:, :, None, :], cos, sin, rot)[:, :, 0, :]
    return c_kv, k_pe, f


def _mla_attend(q_full, scale, latent, p, cfg, ctx, B, L, decode_len=None,
                lengths=None, q_offset=0, verify_len=None):
    """latent: concatenated [c_kv ; k_pe] (B, S, c+dr).  Values are the
    first c dims of the same buffer — attention reads ONE cache tensor
    (no per-step concat of the 32k-deep cache; §Perf iteration C2).
    ``verify_len``: speculative-verify path — L consecutive queries per
    row, query t masked at ``verify_len[b] + t`` (see verify_attention)."""
    c = cfg.kv_lora_rank
    kv = latent[:, :, None, :]                       # KV=1 (MQA)
    vv = latent[:, :, None, :c]
    # flops[mla]: the absorbed attention core + value un-absorption have
    # no fused ABFT kernel (flash routing never reaches MLA) — the
    # auditor reports this whole region as known_unprotected['mla']
    with coverage_scope("mla"):
        if verify_len is not None:
            ctxv = verify_attention(q_full, kv, vv, verify_len,
                                    scale=scale)
        elif decode_len is None:
            ctxv = chunked_attention(
                q_full, kv, vv, causal=True, scale=scale, lengths=lengths,
                q_offset=q_offset)
        else:
            ctxv = decode_attention(q_full, kv, vv, decode_len,
                                    scale=scale)
        # un-absorb values: (B,L,H,c) @ (H,c,dv) -> (B,L,H,dv)
        out = jnp.einsum(
            "blhc,hcv->blhv", ctxv.astype(F32), p["w_uv"].astype(F32),
            preferred_element_type=F32).astype(q_full.dtype)
    out = out.reshape(B, L, -1)
    return dense(out, p["wo"], ctx, "attn_out", tag="mla.out")


def mla_forward(x, p, cfg: ModelConfig, ctx: LayerCtx, positions):
    B, L, _ = x.shape
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    out, f3 = _mla_attend(q_full, scale, latent, p, cfg, ctx, B, L)
    return out, or_flags(f1, f2, f3)


def mla_prefill(x, p, cfg: ModelConfig, ctx: LayerCtx, positions, cache,
                layer, slots=None, lengths=None, starts=None):
    """Latent rows land in entry ``layer`` of the stacked (R, B, S,
    c+dr) cache.  ``starts``: resumable-chunk path (see gqa_prefill) —
    the chunk's latents land behind the resident prefix rows and
    attention runs over the slot's cache with per-row causal offsets."""
    B, L, _ = x.shape
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    if starts is None:
        out, f3 = _mla_attend(
            q_full, scale, latent, p, cfg, ctx, B, L, lengths=lengths)
        if slots is None:
            new_latent = cache_write(cache["latent"], layer, latent)
        else:
            new_latent = _slot_prefill_write(
                cache["latent"], layer, latent, slots)
    else:
        assert slots is not None, "chunked prefill needs slot targets"
        new_latent = _write_rows(
            cache["latent"], layer, slots, starts, latent, lengths)
        out, f3 = _mla_attend(
            q_full, scale,
            jnp.take(cache_layer(new_latent, layer), slots, axis=0), p, cfg,
            ctx, B, L, lengths=starts + lengths, q_offset=starts)
    return out, {"latent": new_latent}, or_flags(f1, f2, f3)


def mla_decode(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache, layer):
    B = x.shape[0]
    pos = _vec_positions(pos, B)
    positions = pos[:, None]
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent_new = jnp.concatenate([c_kv, k_pe], axis=-1)  # (B, 1, c+dr)
    lat = _row_scatter(cache["latent"], layer, latent_new, pos)
    out, f3 = _mla_attend(
        q_full, scale, cache_layer(lat, layer), p, cfg, ctx, B, 1,
        decode_len=pos + 1)
    return out, {"latent": lat}, or_flags(f1, f2, f3)


def mla_verify(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache, layer,
               valid):
    """Speculative verify (dense MLA): the draft window's latents land
    behind the committed prefix (drop-safe window scatter) and each
    query attends its own causal prefix (see gqa_verify)."""
    B, T, _ = x.shape
    pos = _vec_positions(pos, B)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent_new = jnp.concatenate([c_kv, k_pe], axis=-1)  # (B, T, c+dr)
    lat = _window_scatter(cache["latent"], layer, latent_new, pos, valid)
    out, f3 = _mla_attend(
        q_full, scale, cache_layer(lat, layer), p, cfg, ctx, B, T,
        verify_len=pos + 1)
    return out, {"latent": lat}, or_flags(f1, f2, f3)


def mla_paged_prefill(x, p, cfg: ModelConfig, ctx: LayerCtx, positions,
                      cache, layer, tables, lengths, starts=None):
    """Paged MLA prefill: latent rows scatter into entry ``layer`` of the
    stacked (R, NB, BS, c+dr) pool via the admission batch's block
    tables.  ``starts``: prefix-sharing suffix path — suffix latents land
    behind the resident shared prefix and attention runs over the
    gathered logical latent buffer with per-row causal offsets (see
    gqa_paged_prefill)."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_prefill

    B, L, _ = x.shape
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    if starts is None:
        out, f3 = _mla_attend(
            q_full, scale, latent, p, cfg, ctx, B, L, lengths=lengths)
        new_latent = paged_scatter_prefill(
            cache["latent"], layer, latent, tables, lengths)
    else:
        new_latent = paged_scatter_prefill(
            cache["latent"], layer, latent, tables, lengths, starts=starts)
        out, f3 = _mla_attend(
            q_full, scale, paged_gather(new_latent, layer, tables),
            p, cfg, ctx, B, L, lengths=starts + lengths, q_offset=starts)
    return out, {"latent": new_latent}, or_flags(f1, f2, f3)


def mla_paged_decode(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache,
                     layer, tables):
    """Paged MLA decode: scatter the new latent at the cursor's block,
    gather the slot's blocks, attend with per-row length masking."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_decode

    B = x.shape[0]
    pos = _vec_positions(pos, B)
    positions = pos[:, None]
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent_new = jnp.concatenate([c_kv, k_pe], axis=-1)  # (B, 1, c+dr)
    lat = paged_scatter_decode(cache["latent"], layer, latent_new[:, 0],
                               tables, pos)
    out, f3 = _mla_attend(
        q_full, scale, paged_gather(lat, layer, tables), p, cfg, ctx,
        B, 1, decode_len=pos + 1)
    return out, {"latent": lat}, or_flags(f1, f2, f3)


def mla_paged_verify(x, p, cfg: ModelConfig, ctx: LayerCtx, pos, cache,
                     layer, valid, tables):
    """Paged speculative verify (MLA): draft latents scatter behind the
    committed prefix via the block tables, then every query attends the
    gathered logical latent buffer with its own per-query mask."""
    from repro.serve.paged_cache import paged_gather, paged_scatter_prefill

    B, T, _ = x.shape
    pos = _vec_positions(pos, B)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q_full, scale, f1 = _mla_q(x, p, cfg, ctx, positions)
    c_kv, k_pe, f2 = _mla_latent_kv(x, p, cfg, ctx, positions)
    latent_new = jnp.concatenate([c_kv, k_pe], axis=-1)  # (B, T, c+dr)
    lat = paged_scatter_prefill(cache["latent"], layer, latent_new, tables,
                                valid, starts=pos)
    out, f3 = _mla_attend(
        q_full, scale, paged_gather(lat, layer, tables), p, cfg, ctx,
        B, T, verify_len=pos + 1)
    return out, {"latent": lat}, or_flags(f1, f2, f3)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {
        "latent": jnp.zeros(
            (batch, max_len, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            dtype),
    }
