"""jit-ready wrapper for the fused-ABFT flash attention kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.checksums import ATOL, CheckResult, flag_from, tolerance_scale
from repro.core.faults import FaultSpec
from repro.kernels.flash_attention import (
    F32,
    flash_attention_kernel,
    flash_decode_kernel,
    flash_decode_paged_kernel,
)
from repro.kernels.ops import default_interpret


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _attn_check(rs, bs, rp, bp, d: int, s: int,
                c_factor: float) -> CheckResult:
    """Fold the per-tile residual/bound vectors of both attention GEMMs
    (scores: reduction depth ``d``; PV: reduction depth ``s``) into one
    CheckResult — shared by every flash entry point.  The kernels emit
    lane-replicated (rows, 128) tiles; lane 0 carries the values."""
    rs, bs, rp, bp = (a[..., 0] for a in (rs, bs, rp, bp))
    tau_s = ATOL + tolerance_scale(d, c=c_factor) * bs
    tau_pv = ATOL + tolerance_scale(s, c=c_factor) * bp
    flag = jnp.logical_or(flag_from(rs, tau_s), flag_from(rp, tau_pv))
    residual = jnp.stack([jnp.max(rs), jnp.max(rp)])
    threshold = jnp.stack([jnp.min(tau_s), jnp.min(tau_pv)])
    return CheckResult(flag=flag, residual=residual, threshold=threshold)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    fault: FaultSpec | None = None,
    c_factor: float = 16.0,
):
    """Fused-ABFT attention.  q: (B, Lq, H, D); k/v: (B, Lk, KV, D[v]).

    GQA: kv heads are repeated to H (view-level).  Returns
    (out (B, Lq, H, Dv), CheckResult) where the residuals cover both
    attention GEMMs (scores and PV).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Lq, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[3]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    bq_eff = min(bq, _round_up(Lq, 8))
    bk_eff = min(bk, _round_up(k.shape[1], 8))
    pq = _round_up(Lq, bq_eff) - Lq
    pk = _round_up(k.shape[1], bk_eff) - k.shape[1]
    # pad K positions with -inf-free zeros; padded keys are masked by the
    # causal test (k_pos > any q_pos) or contribute exp(-large)≈... for
    # non-causal we mask via an extra key-position guard below.
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    assert causal or pk == 0, "non-causal padding not supported; pad caller"

    if fault is None:
        fault = FaultSpec.none()
    fi = jnp.stack([
        fault.row // bq_eff,
        jnp.zeros((), jnp.int32),
        fault.row % bq_eff,
        fault.col,
        fault.enabled,
        jax.lax.bitcast_convert_type(fault.delta.astype(F32), jnp.int32),
    ]).astype(jnp.int32)

    def one_head(qh, kh, vh):
        return flash_attention_kernel(
            qh, kh, vh, fi, bq=bq_eff, bk=bk_eff, causal=causal,
            interpret=interpret, out_dtype=q.dtype)

    # vmap over batch then heads (head axis moved in front of L)
    f = jax.vmap(jax.vmap(one_head, in_axes=(0, 0, 0)), in_axes=(0, 0, 0))
    o, rs, bs, rp, bp = f(
        jnp.moveaxis(qp, 2, 1), jnp.moveaxis(kp, 2, 1),
        jnp.moveaxis(vp, 2, 1))
    o = jnp.moveaxis(o, 1, 2)[:, :Lq]

    return o, _attn_check(rs, bs, rp, bp, D, k.shape[1], c_factor)


def flash_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    bk: int = 128,
    interpret: bool | None = None,
    c_factor: float = 16.0,
):
    """Fused-ABFT decode attention against a ragged KV cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S, KV, D[v]); lengths: (B,)
    int32 per-row valid cache length (the serving engine's vectorized
    cursor + 1).  Each batch row attends only its own valid prefix, so
    mixed-length continuous batching is exact.  Returns
    (out (B, 1, H, Dv), CheckResult) covering both attention GEMMs.
    """
    if interpret is None:
        interpret = default_interpret()
    B, _, H, D = q.shape
    S, KV, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
    G = H // KV

    bk_eff = min(bk, _round_up(S, 8))
    pk = _round_up(S, bk_eff) - S
    kp = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32), (B,))[:, None]    # (B, 1)
    # q heads are stored kv-major (kv, group): each kernel call takes the
    # G query heads of one kv head, so the cache is never head-replicated
    qg = q[:, 0].reshape(B, KV, G, D)

    def one_kv_head(qk, kh, vh, ln):
        return flash_decode_kernel(
            qk, kh, vh, ln, bk=bk_eff, interpret=interpret,
            out_dtype=q.dtype)

    f = jax.vmap(jax.vmap(one_kv_head, in_axes=(0, 0, 0, None)),
                 in_axes=(0, 0, 0, 0))
    o, rs, bs, rp, bp = f(
        qg, jnp.moveaxis(kp, 2, 1), jnp.moveaxis(vp, 2, 1), lengths)
    out = o.reshape(B, 1, H, Dv)             # (B, KV, G, Dv), kv-major

    return out, _attn_check(rs, bs, rp, bp, D, S, c_factor)


def flash_decode_paged(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    interpret: bool | None = None,
    c_factor: float = 16.0,
):
    """Fused-ABFT decode attention against a PAGED KV cache.

    q: (B, 1, H, D); k_pool/v_pool: (NB, BS, KV, D[v]) physical block
    pools shared by all rows (serve/paged_cache.py layout);
    block_tables: (B, W) int32 per-row physical block ids (sentinel-
    padded tails are clamped here — the per-row ``lengths`` mask makes
    their contribution exactly zero); lengths: (B,) valid logical cache
    lengths (the engine's vectorized cursor + 1).  The kernel takes the
    table as a scalar-prefetch index operand, so each grid step DMAs one
    physical block — the pool is never gathered to a dense copy, and GQA
    query heads are grouped per kv head (q tile (G, D)) so the pool is
    never head-replicated either.
    Returns (out (B, 1, H, Dv), CheckResult) covering both attention
    GEMMs.
    """
    if interpret is None:
        interpret = default_interpret()
    B, _, H, D = q.shape
    NB, BS, KV, Dv = v_pool.shape
    W = block_tables.shape[1]
    G = H // KV

    tables = jnp.clip(
        jnp.asarray(block_tables, jnp.int32), 0, NB - 1)       # (B, W)
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32), (B,))[:, None]        # (B, 1)
    # q heads are stored kv-major (kv, group): group them per kv head so
    # every kernel call shares one un-copied pool slice
    qg = q[:, 0].reshape(B, KV, G, D)

    def one_kv_head(qk, kh, vh, tb, ln):
        return flash_decode_paged_kernel(
            qk, kh, vh, tb, ln, interpret=interpret, out_dtype=q.dtype)

    # vmap batch (tables/lengths per-row, pools shared), then kv heads
    # (pool slice per kv head, table shared)
    f = jax.vmap(jax.vmap(one_kv_head, in_axes=(0, 0, 0, None, None)),
                 in_axes=(0, None, None, 0, 0))
    o, rs, bs, rp, bp = f(
        qg, jnp.moveaxis(k_pool, 2, 0), jnp.moveaxis(v_pool, 2, 0),
        tables, lengths)
    out = o.reshape(B, 1, H, Dv)             # (B, KV, G, Dv), kv-major

    return out, _attn_check(rs, bs, rp, bp, D, W * BS, c_factor)
