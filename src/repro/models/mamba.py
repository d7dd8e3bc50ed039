"""Mamba2 (SSD — state-space duality) block, chunked for TPU.

The SSD algorithm (Dao & Gu, arXiv:2405.21060) decomposes the selective
scan into intra-chunk GEMMs (MXU-friendly, quadratic within a chunk) plus a
sequential inter-chunk state recurrence (lax.scan).  The in/out projections
are ABFT-protected GEMMs; the intra-chunk einsums are the Mamba analogue of
attention score/PV matmuls.

Decode maintains (conv_state, ssm_state) — constant-size per request, which
is why the SSM archs own the long_500k shapes (DESIGN.md §4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.markers import coverage_scope
from repro.configs.base import ModelConfig
from repro.models.layers import (
    LayerCtx,
    cache_layer,
    cache_write,
    dense,
    gated_rms_norm,
    or_flags,
)

F32 = jnp.float32


def _init(key, shape, scale=0.02, dtype=jnp.bfloat16):
    return (scale * jax.random.normal(key, shape, F32)).astype(dtype)


def conv_dim(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_state


def init_mamba(cfg: ModelConfig, key, dtype) -> dict:
    """Projections are stored split (z / x / BC / dt and conv_x / conv_bc)
    rather than fused, so tensor-parallel sharding of the head dims never
    slices across semantic boundaries (see sharding.py)."""
    d_in, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 7)
    return {
        "in_z": _init(ks[0], (cfg.d_model, d_in), dtype=dtype),
        "in_x": _init(ks[1], (cfg.d_model, d_in), dtype=dtype),
        "in_bc": _init(ks[2], (cfg.d_model, 2 * n), dtype=dtype),
        "in_dt": _init(ks[3], (cfg.d_model, h), dtype=dtype),
        "conv_x_w": _init(
            ks[4], (cfg.ssm_conv_width, d_in), scale=0.5, dtype=dtype),
        "conv_x_b": jnp.zeros((d_in,), dtype),
        "conv_bc_w": _init(
            ks[5], (cfg.ssm_conv_width, 2 * n), scale=0.5, dtype=dtype),
        "conv_bc_b": jnp.zeros((2 * n,), dtype),
        "A_log": jnp.zeros((h,), F32),            # A = -exp(A_log) = -1
        "D": jnp.ones((h,), F32),
        "dt_bias": jnp.full((h,), -4.0, F32),     # softplus^-1(~0.018)
        "out_norm": jnp.ones((d_in,), dtype),
        "out_proj": _init(ks[6], (d_in, cfg.d_model), dtype=dtype),
    }


def _project_in(x, p, cfg: ModelConfig, ctx: LayerCtx):
    """Split input projections; returns (z, xs, Bm, Cm, dt, flag)."""
    n = cfg.ssm_state
    z, f1 = dense(x, p["in_z"], ctx, "ssm_in", tag="ssm.in_z")
    xs, f2 = dense(x, p["in_x"], ctx, "ssm_in", tag="ssm.in_x")
    bc, f3 = dense(x, p["in_bc"], ctx, "ssm_in", tag="ssm.in_bc")
    dt, f4 = dense(x, p["in_dt"], ctx, "ssm_in", tag="ssm.in_dt")
    return z, xs, bc[..., :n], bc[..., n:], dt, or_flags(f1, f2, f3, f4)


def _causal_conv(u, w, b):
    """Depthwise causal conv, width W.  u: (B, L, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(u, dtype=F32)
    for i in range(W):  # W is tiny (4): unrolled adds, fuses well
        out = out + pad[:, i: i + u.shape[1], :].astype(F32) * w[i].astype(F32)
    return jax.nn.silu(out + b.astype(F32)).astype(u.dtype)


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int):
    """Chunked SSD scan.

    xh: (B, L, H, P); dt: (B, L, H) (post-softplus); A: (H,) negative;
    Bm/Cm: (B, L, N) (single group).  Returns (B, L, H, P) and the final
    state (B, H, P, N).

    flops[ssm_scan]: the intra-chunk einsums are weight-free data-data
    contractions (the SSM analogue of attention score/PV matmuls) with no
    ABFT kernel — the coverage auditor reports them as a known gap rather
    than a regression.
    """
    with coverage_scope("ssm_scan"):
        return _ssd_chunked_impl(xh, dt, A, Bm, Cm, chunk)


def _ssd_chunked_impl(xh, dt, A, Bm, Cm, chunk):
    Bsz, L, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = (L + pad) // Q

    xc = xh.reshape(Bsz, nc, Q, H, P).astype(F32)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(F32)
    Bc = Bm.reshape(Bsz, nc, Q, N).astype(F32)
    Cc = Cm.reshape(Bsz, nc, Q, N).astype(F32)

    dA = dtc * A[None, None, None, :]                 # (B, c, Q, H)
    cs = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    cs_end = cs[:, :, -1:, :]                         # (B, c, 1, H)

    # intra-chunk (quadratic, MXU): L_mat[q,s] = exp(cs_q - cs_s), q >= s
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (B,c,Q,S,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L_mat = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc,
                        preferred_element_type=F32)
    xdt = xc * dtc[..., None]                         # (B,c,Q,H,P)
    y_diag = jnp.einsum("bcqs,bcqsh,bcshp->bcqhp", scores, L_mat, xdt,
                        preferred_element_type=F32)

    # per-chunk state contribution and decay
    decay_out = jnp.exp(cs_end - cs)                  # (B,c,Q,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchpn", Bc, decay_out, xdt,
                        preferred_element_type=F32)
    chunk_decay = jnp.exp(cs_end[:, :, 0, :])         # (B,c,H)

    # inter-chunk recurrence (sequential scan over chunks)
    def step(S_prev, xs):
        st, dec = xs                                  # (B,H,P,N), (B,H)
        S_new = S_prev * dec[:, :, None, None] + st
        return S_new, S_prev

    S0 = jnp.zeros((Bsz, H, P, N), F32)
    S_final, S_prevs = jax.lax.scan(
        step,
        S0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    S_prevs = S_prevs.transpose(1, 0, 2, 3, 4)        # (B,c,H,P,N)

    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc, S_prevs, jnp.exp(cs),
                       preferred_element_type=F32)

    y = (y_diag + y_off).reshape(Bsz, nc * Q, H, P)[:, :L]
    return y, S_final


def mamba_forward(x, p, cfg: ModelConfig, ctx: LayerCtx):
    """Full-sequence Mamba2 mixer.  x: (B, L, D) -> (B, L, D)."""
    Bsz, L, _ = x.shape
    H, P, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z, xs, Bm, Cm, dt, f1 = _project_in(x, p, cfg, ctx)
    xs = _causal_conv(xs, p["conv_x_w"], p["conv_x_b"])
    bc = _causal_conv(
        jnp.concatenate([Bm, Cm], axis=-1), p["conv_bc_w"], p["conv_bc_b"])
    Bm, Cm = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(Bsz, L, H, P)
    y, _ = _ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"][None, None, :, None] * xh.astype(F32)
    y = y.reshape(Bsz, L, cfg.d_inner).astype(x.dtype)
    y = gated_rms_norm(y, z, p["out_norm"], cfg.norm_eps)
    out, f2 = dense(y, p["out_proj"], ctx, "ssm_out", tag="ssm.out")
    return out, or_flags(f1, f2)


def mamba_prefill(x, p, cfg: ModelConfig, ctx: LayerCtx, cache, layer,
                  slots=None, lengths=None):
    """Prefill: full-sequence forward + final (conv, ssm) states, written
    into entry ``layer`` of the stacked (R, B, ...) state leaves.

    ``slots``/``lengths`` (continuous-batching path): x is the admission
    batch (A, L, D) padded to a common L.  Padded positions are masked out
    of the recurrence (dt := 0 there, so the state neither decays nor
    accumulates past lengths[b]); the conv window is taken per-row at the
    true prompt end; states scatter into engine cache rows ``slots``.

    Paged engines (serve/paged_cache.py) use this same path: mamba state
    is constant-size per request — one implicit permanently-resident
    block per slot — so there is nothing to page and no block table to
    consult."""
    Bsz, L, _ = x.shape
    H, P, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    W = cfg.ssm_conv_width
    z, xs, Bm, Cm, dt, f1 = _project_in(x, p, cfg, ctx)
    bc_in = jnp.concatenate([Bm, Cm], axis=-1)
    valid = None
    if lengths is not None:
        valid = (jnp.arange(L)[None, :] < lengths[:, None])   # (A, L)
        vz = valid[..., None].astype(xs.dtype)
        xs = xs * vz
        bc_in = bc_in * vz.astype(bc_in.dtype)
    # conv states: last W-1 raw inputs of each stream (per-row window
    # ending at the true prompt length when ragged)
    pad_xs = jnp.pad(xs, ((0, 0), (W - 1, 0), (0, 0)))
    pad_bc = jnp.pad(bc_in, ((0, 0), (W - 1, 0), (0, 0)))
    if lengths is None:
        conv_x_state = jax.lax.dynamic_slice_in_dim(pad_xs, L, W - 1, axis=1)
        conv_bc_state = jax.lax.dynamic_slice_in_dim(pad_bc, L, W - 1, axis=1)
    else:
        row_slice = jax.vmap(
            lambda r, s: jax.lax.dynamic_slice_in_dim(r, s, W - 1, axis=0))
        conv_x_state = row_slice(pad_xs, lengths)
        conv_bc_state = row_slice(pad_bc, lengths)
    xs2 = _causal_conv(xs, p["conv_x_w"], p["conv_x_b"])
    bc = _causal_conv(bc_in, p["conv_bc_w"], p["conv_bc_b"])
    Bm2, Cm2 = bc[..., :n], bc[..., n:]
    dt2 = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    if valid is not None:
        # dt == 0 past the prompt end: exp(0)=1 decay, zero input term —
        # the state is frozen at its lengths[b]-token value through padding
        dt2 = dt2 * valid.astype(F32)[..., None]
    A = -jnp.exp(p["A_log"])
    xh = xs2.reshape(Bsz, L, H, P)
    y, S_final = _ssd_chunked(xh, dt2, A, Bm2, Cm2, cfg.ssm_chunk)
    y = y + p["D"][None, None, :, None] * xh.astype(F32)
    y = y.reshape(Bsz, L, cfg.d_inner).astype(x.dtype)
    y = gated_rms_norm(y, z, p["out_norm"], cfg.norm_eps)
    out, f2 = dense(y, p["out_proj"], ctx, "ssm_out", tag="ssm.out")
    conv_x_state = conv_x_state.astype(cache["conv_x"].dtype)
    conv_bc_state = conv_bc_state.astype(cache["conv_bc"].dtype)
    S_final = S_final.astype(cache["ssm"].dtype)
    states = {"conv_x": conv_x_state, "conv_bc": conv_bc_state,
              "ssm": S_final}
    if slots is None:
        new_cache = {k: cache_write(cache[k], layer, st)
                     for k, st in states.items()}
    else:
        new_cache = {k: cache[k].at[layer, slots].set(st)
                     for k, st in states.items()}
    return out, new_cache, or_flags(f1, f2)


def _conv_step(state, new, w, b):
    """Rolling depthwise conv step.  state: (B, W-1, C); new: (B, C)."""
    with coverage_scope("ssm_scan"):
        window = jnp.concatenate(
            [state.astype(F32), new[:, None, :].astype(F32)], axis=1)
        out = jnp.einsum("bwc,wc->bc", window, w.astype(F32))
        out = jax.nn.silu(out + b.astype(F32))
        return out, window[:, 1:, :]


def mamba_decode(x, p, cfg: ModelConfig, ctx: LayerCtx, cache, layer):
    """One-token recurrent step.  x: (B, 1, D); reads and rewrites entry
    ``layer`` of the stacked state leaves.  Serves the dense AND paged
    engines alike (per-slot constant-size state; see mamba_prefill)."""
    Bsz = x.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z, xs, Bm, Cm, dt, f1 = _project_in(x, p, cfg, ctx)
    z, xs, dt = z[:, 0], xs[:, 0], dt[:, 0]
    bc_in = jnp.concatenate([Bm[:, 0], Cm[:, 0]], axis=-1)

    xs2, new_conv_x = _conv_step(
        cache_layer(cache["conv_x"], layer), xs, p["conv_x_w"],
        p["conv_x_b"])
    bc2, new_conv_bc = _conv_step(
        cache_layer(cache["conv_bc"], layer), bc_in, p["conv_bc_w"],
        p["conv_bc_b"])
    Bm2, Cm2 = bc2[..., :N], bc2[..., N:]

    dt2 = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])   # (B, H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt2 * A[None, :])                         # (B, H)
    xh = xs2.reshape(Bsz, H, P)
    S = cache_layer(cache["ssm"], layer).astype(F32)       # (B,H,P,N)
    with coverage_scope("ssm_scan"):
        S = S * dA[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt2, Bm2, xh, preferred_element_type=F32)
        y = jnp.einsum("bn,bhpn->bhp", Cm2, S,
                       preferred_element_type=F32)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(Bsz, 1, cfg.d_inner).astype(x.dtype)
    y = gated_rms_norm(y, z[:, None, :], p["out_norm"], cfg.norm_eps)
    out, f2 = dense(y, p["out_proj"], ctx, "ssm_out", tag="ssm.out")
    states = {"conv_x": new_conv_x, "conv_bc": new_conv_bc, "ssm": S}
    new_cache = {k: cache_write(cache[k], layer, st)
                 for k, st in states.items()}
    return out, new_cache, or_flags(f1, f2)


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype):
    return {
        "conv_x": jnp.zeros(
            (batch, cfg.ssm_conv_width - 1, cfg.d_inner), dtype),
        "conv_bc": jnp.zeros(
            (batch, cfg.ssm_conv_width - 1, 2 * cfg.ssm_state), dtype),
        "ssm": jnp.zeros(
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32),
    }
