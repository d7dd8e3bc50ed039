"""Shared NN building blocks.  Every GEMM routes through the ABFT-protected
matmul (core/protected.py) — the paper's technique as a first-class layer
feature.  All functions are pure; params are plain pytrees (dicts).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.analysis.markers import coverage_scope
from repro.core.faults import FaultSpec
from repro.core.protected import ABFTConfig, protected_matmul

F32 = jnp.float32

# ---------------------------------------------------------------- fault plumbing
# Injection sites (static ids) — where in a layer a campaign can corrupt a
# GEMM output.  The paper's fault model is one faulty output value per
# linear layer; campaigns pick (layer, site, row, col).

SITES = {
    "qkv": 0, "attn_out": 1, "mlp_up": 2, "mlp_down": 3,
    "router": 4, "expert_up": 5, "expert_down": 6,
    "lm_head": 7, "ssm_in": 8, "ssm_out": 9,
    "cross_qkv": 10, "cross_out": 11, "q_a": 12, "kv_a": 13,
}


class ModelFault(NamedTuple):
    """A single-fault campaign target inside a full model."""

    layer: jnp.ndarray          # global layer index (int32 scalar)
    site: jnp.ndarray           # SITES id (int32 scalar)
    spec: FaultSpec

    @staticmethod
    def none() -> "ModelFault":
        z = jnp.zeros((), jnp.int32)
        return ModelFault(layer=z, site=z, spec=FaultSpec.none())

    @staticmethod
    def at(layer: int, site: str, spec: FaultSpec) -> "ModelFault":
        return ModelFault(
            layer=jnp.asarray(layer, jnp.int32),
            site=jnp.asarray(SITES[site], jnp.int32),
            spec=spec,
        )


@dataclasses.dataclass(frozen=True)
class ShardingHints:
    """Static annotation hints for with_sharding_constraint inside layers
    (only where GSPMD propagation needs help, e.g. MoE dispatch buffers).
    ``dp``: data-parallel axes for token dims; ``dp_size``: their product
    (the MoE group count); ``ep``: expert axes; ``moe_mode``: 'ep'
    (experts sharded) or 'tp' (expert ffn dim sharded)."""

    dp: tuple = ("data",)
    dp_size: int = 1
    ep: tuple = ("model",)
    tp: str = "model"
    moe_mode: str = "ep"

    def constrain(self, x, *spec):
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain(ctx, x, *spec):
    """Apply a sharding constraint if hints are active (no-op on CPU/tests)."""
    if ctx.hints is None:
        return x
    return ctx.hints.constrain(x, *spec)


@dataclasses.dataclass(frozen=True)
class LayerCtx:
    """Per-forward context: the static ABFT config (a facade over the
    active ProtectionPolicy, core/policy.py) + traced fault target +
    traced current layer index (set inside scanned stacks)."""

    abft: ABFTConfig = ABFTConfig()
    fault: ModelFault | None = None
    layer_idx: jnp.ndarray | None = None   # traced global layer index
    hints: ShardingHints | None = None
    # the one device the serving cache lives on, whose storage layout the
    # layer loop's carried cache keeps (keep_storage_layout); None when
    # the cache is sharded over a mesh or there is none
    cache_device: jax.Device | None = None
    # static prefix for plan-facing site tags ("enc." inside the whisper
    # encoder stack) so the coverage auditor can tell encoder GEMMs from
    # identically-shaped decoder ones
    site_prefix: str = ""

    def with_layer(self, idx) -> "LayerCtx":
        return dataclasses.replace(self, layer_idx=idx)


def dense(x, w, ctx: LayerCtx, site: str, b=None, out_dtype=None,
          tag: str | None = None):
    """ABFT-protected ``x @ w (+ b)``.  Returns (y, flag: scalar bool).

    Scheme selection happens at trace time via the config's
    ProtectionPolicy (``ctx.abft.effective_policy()``).  Layers inside
    scanned stacks share one trace, so per-layer static distinctions —
    like the first protected layer's extra activation-checksum read —
    live in the analytic ``ProtectionPlan`` (explicit ``LayerSpec.first``
    descriptors), not here.

    ``site`` is the fault-injection site id (SITES); ``tag`` is the
    plan-facing layer name (counting.layer_gemms keys, e.g. ``attn.q``)
    stamped into the ``abft[...]`` trace marker for the coverage auditor
    — it defaults to the fault site so an untagged call is still marked
    (and shows up as a trace-only site in plan cross-validation, which
    is precisely the drift the auditor exists to catch)."""
    fault = None
    if ctx.fault is not None:
        here = ctx.fault.site == SITES[site]
        if ctx.layer_idx is not None:
            here = here & (ctx.fault.layer == ctx.layer_idx)
        spec = ctx.fault.spec
        fault = spec._replace(
            enabled=(spec.enabled.astype(bool) & here).astype(jnp.int32))
    y, chk = protected_matmul(
        x, w, ctx.abft, out_dtype=out_dtype or x.dtype, fault=fault,
        site=ctx.site_prefix + (tag or site))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y, chk.flag


# ---------------------------------------------------------------- norms

def rms_norm(x, w, eps: float = 1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * w.astype(x.dtype) + b.astype(x.dtype)


def norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        return layer_norm(x, p["w"], p["b"], eps)
    return rms_norm(x, p["w"], eps)


def gated_rms_norm(x, z, w, eps: float = 1e-6):
    """Mamba2 output norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(F32)).astype(x.dtype), w, eps)


# ---------------------------------------------------------------- rope

def rope_tables(positions, head_dim: int, theta: float, pct: float = 1.0):
    """positions: (..., L) int32 -> (cos, sin) of shape (..., L, rot/2)."""
    rot = int(head_dim * pct) // 2 * 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = positions.astype(F32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang), rot


def apply_rope(x, cos, sin, rot: int):
    """x: (B, L, H, D); rotate first ``rot`` dims (split-half convention)."""
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < x.shape[-1] else out


# ------------------------------------------------------------ stacked cache
# Cache leaves stay stacked over a segment's repeats, (R, ...), and ride
# the layer scan as carry: each layer reads its own entry where it is
# consumed and writes only the rows it produces, so XLA updates the
# loop-carried buffer in place instead of copying a whole layer out of
# the stack and back every step.

def storage_layout(leaf, device):
    """The layout ``device`` stores an array of ``leaf``'s shape and dtype
    in: what the engine's cache arrays arrive in.  (A TPU v5e keeps a
    [.., S, 32, 64] bf16 cache with positions minor, because a 64-wide
    minor dim would be padded to 128.)"""
    return Layout.from_pjrt_layout(
        device.client.get_default_layout(leaf.dtype, leaf.shape, device))


def keep_storage_layout(tree, device):
    """Pin loop-carried cache leaves to ``device``'s storage layout.
    Otherwise the compiler may give the loop state the layout its readers
    prefer (a dot's, or a padded one) and relayout the whole cache on the
    way into and out of the layer loop: a copy of every layer per call,
    and one more cache-sized buffer.

    ``device`` is ``LayerCtx.cache_device``, set by the executor that
    places the cache.  ``None`` (a cache sharded over a mesh: the SPMD
    partitioner cannot split the pin) leaves the layout to the compiler."""
    if device is None:
        return tree
    return jax.tree_util.tree_map(
        lambda a: with_layout_constraint(a, storage_layout(a, device)), tree)


def cache_layer(leaf, layer):
    """Entry ``layer`` of a stacked cache leaf (R, ...) -> (...)."""
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)


def cache_write(leaf, layer, new):
    """Write ``new`` at the origin of entry ``layer`` of a stacked leaf:
    ``new`` may be shorter than the entry along any dim (a prompt of L
    tokens into an S-deep row)."""
    start = (layer,) + (0,) * new.ndim
    return jax.lax.dynamic_update_slice(
        leaf, new[None].astype(leaf.dtype), start)


# ---------------------------------------------------------------- attention

NEG_INF = -1e30


def chunked_attention(
    q, k, v, *, causal: bool, q_offset=0, q_chunk: int = 512,
    k_chunk: int = 1024, scale: float | None = None, lengths=None,
):
    """Memory-bounded attention (pure-JAX flash style): nested scans over
    query and key chunks with online softmax.  Avoids materializing the
    (Lq, Lk) score matrix — required for the 32k prefill shapes.

    The whole body runs inside a ``flops[softmax]`` coverage scope: the
    score/PV einsums are outside the matmul-ABFT surface by design —
    they are the ops the fused flash-ABFT kernels replace when
    ``flash_attention=True`` — and the auditor allowlists them under
    that kind instead of flagging them unprotected.

    q: (B, Lq, H, Dk); k: (B, Lk, KV, Dk); v: (B, Lk, KV, Dv).
    GQA: H must be a multiple of KV; KV == 1 is MQA (used by absorbed MLA).
    ``lengths``: optional (B,) int32 per-row valid key count — keys at
    positions >= lengths[b] are masked for row b (ragged batched prefill).
    ``q_offset``: logical position of query 0 — a scalar, or a (B,) int32
    vector when every row starts at its own position (the prefix-sharing
    suffix prefill: row b's query t sits at logical position
    ``q_offset[b] + t`` for the causal mask; keys are addressed from
    logical 0).  The scalar path is untouched bit-for-bit.
    Returns (B, Lq, H, Dv).
    """
    with coverage_scope("softmax"):
        return _chunked_attention_impl(
            q, k, v, causal=causal, q_offset=q_offset, q_chunk=q_chunk,
            k_chunk=k_chunk, scale=scale, lengths=lengths)


def _chunked_attention_impl(
    q, k, v, *, causal, q_offset, q_chunk, k_chunk, scale, lengths,
):
    B, Lq, H, Dk = q.shape
    row_offset = getattr(q_offset, "ndim", 0) > 0          # (B,) vector?
    _, Lk, KV, Dv = v.shape[0], v.shape[1], v.shape[2], v.shape[3]
    groups = H // KV
    scale = scale if scale is not None else Dk ** -0.5

    q_chunk = min(q_chunk, Lq)
    k_chunk = min(k_chunk, Lk)
    # pad to chunk multiples
    pq = -Lq % q_chunk
    pk = -Lk % k_chunk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_chunk, kp.shape[1] // k_chunk

    qc = qp.reshape(B, nq, q_chunk, H, Dk).transpose(1, 0, 2, 3, 4)
    kc = kp.reshape(B, nk, k_chunk, KV, Dk).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, nk, k_chunk, KV, Dv).transpose(1, 0, 2, 3, 4)

    kv_valid = Lk  # positions >= Lk are padding

    def q_step(_, qi_q):
        qi, qblk = qi_q
        q_base = qi * q_chunk + jnp.arange(q_chunk)
        if row_offset:
            q_pos = q_offset[:, None] + q_base[None, :]    # (B, qc)
        else:
            q_pos = q_offset + q_base                      # (qc,)

        def k_step(carry, ki_kv):
            m, l, acc = carry
            ki, kblk, vblk = ki_kv
            k_pos = ki * k_chunk + jnp.arange(k_chunk)
            # scores: (B, qc, H, kc) = qblk @ kblk^T (kv-head broadcast).
            # NOTE: operands stay in their storage dtype — XLA computes
            # bf16 x bf16 -> f32 natively on the MXU; an explicit
            # .astype(F32) would materialize f32 copies of every k/v
            # chunk to HBM (measured: dominant memory-term contributor,
            # EXPERIMENTS.md §Perf iteration A2/C2).
            qg = qblk.reshape(B, q_chunk, KV, groups, Dk)
            s = jnp.einsum(
                "bqkgd,bskd->bqkgs", qg, kblk,
                preferred_element_type=F32) * scale
            if row_offset:
                mask = jnp.broadcast_to(
                    k_pos[None, None, :] < kv_valid, (B,) + (q_chunk,)
                    + (k_chunk,))
                if causal:
                    mask = mask & (q_pos[:, :, None] >= k_pos[None, None, :])
                s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
            else:
                mask = k_pos[None, :] < kv_valid
                if causal:
                    mask = mask & (q_pos[:, None] >= k_pos[None, :])
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            if lengths is not None:
                row_ok = k_pos[None, :] < lengths[:, None]     # (B, kc)
                s = jnp.where(row_ok[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            # probs stay f32 into PV: bf16 probs regressed the backward
            # pass by 18% (extra convert round-trips in dp/dv), measured
            # in §Perf iteration B3 -> B4.
            pv = jnp.einsum(
                "bqkgs,bskv->bqkgv", p, vblk,
                preferred_element_type=F32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, q_chunk, KV, groups), NEG_INF, F32)
        l0 = jnp.zeros((B, q_chunk, KV, groups), F32)
        a0 = jnp.zeros((B, q_chunk, KV, groups, Dv), F32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.reshape(B, q_chunk, H, Dv).astype(q.dtype)

    _, out = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Lq]


def decode_attention(q, k_cache, v_cache, length, scale=None):
    """Single-token attention against a (B, S, KV, D) cache.

    q: (B, 1, H, Dk); ``length``: number of valid cache positions
    (scalar or (B,)).  Returns (B, 1, H, Dv).

    Runs inside a ``flops[softmax]`` coverage scope (see
    chunked_attention) — ``flash_decode`` is the fused-ABFT replacement.
    """
    with coverage_scope("softmax"):
        B, _, H, Dk = q.shape
        S, KV, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
        groups = H // KV
        scale = scale if scale is not None else Dk ** -0.5
        qg = q.reshape(B, KV, groups, Dk)
        # storage-dtype operands: no materialized f32 cache copy (above)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=F32) * scale
        pos = jnp.arange(S)
        valid = pos[None, :] < jnp.reshape(length, (-1, 1))
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskv->bkgv", p.astype(v_cache.dtype),
                         v_cache, preferred_element_type=F32)
        return out.reshape(B, 1, H, Dv).astype(q.dtype)


def verify_attention(q, k_cache, v_cache, length, scale=None):
    """Speculative-verify attention: T consecutive queries per row
    against a (B, S, KV, D) cache.

    q: (B, T, H, Dk) — row b's queries sit at logical positions
    ``length[b] - 1 .. length[b] + T - 2`` (``length`` is the valid
    cache count for the FIRST query, i.e. its prefix plus its own
    freshly written key, exactly what ``decode_attention`` receives);
    query t may attend ``length[b] + t`` positions.  Returns
    (B, T, H, Dv).

    Deliberately replicates ``decode_attention``'s op sequence —
    storage-dtype score operands, one full softmax (no online
    accumulation), probs cast to the cache dtype before PV — instead of
    reusing ``chunked_attention`` (f32 probs + online softmax): each
    accepted row of a T>1 verify call must be bitwise identical to the
    decode path's output at the same position, the byte-identical-
    stream contract speculative decoding is gated on.  T=1 degenerates
    to ``decode_attention`` exactly.
    """
    with coverage_scope("softmax"):
        B, T, H, Dk = q.shape
        S, KV, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
        groups = H // KV
        scale = scale if scale is not None else Dk ** -0.5
        qg = q.reshape(B, T, KV, groups, Dk)
        # storage-dtype operands: no materialized f32 cache copy (above)
        s = jnp.einsum("btkgd,bskd->btkgs", qg, k_cache,
                       preferred_element_type=F32) * scale
        pos = jnp.arange(S)
        lim = (jnp.reshape(length, (-1, 1))
               + jnp.arange(T, dtype=jnp.int32)[None, :])     # (B, T)
        valid = pos[None, None, :] < lim[:, :, None]
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("btkgs,bskv->btkgv", p.astype(v_cache.dtype),
                         v_cache, preferred_element_type=F32)
        return out.reshape(B, T, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------- mlp

def mlp(x, p, ctx: LayerCtx, act: str = "silu",
        tags: tuple = ("mlp.up", "mlp.down")):
    """SwiGLU (silu) or plain GELU MLP; GEMMs are ABFT-protected.
    ``tags``: plan-facing (up, down) site tags — MoE shared experts pass
    ("moe.shared_up", "moe.shared_down") so the auditor matches them to
    their own plan entries."""
    up_tag, down_tag = tags
    flags = []
    if act == "silu":
        up, f1 = dense(x, p["up"], ctx, "mlp_up", tag=up_tag)
        gate, f2 = dense(x, p["gate"], ctx, "mlp_up", tag=up_tag)
        h = jax.nn.silu(gate.astype(F32)).astype(x.dtype) * up
        flags += [f1, f2]
    else:
        h, f1 = dense(x, p["up"], ctx, "mlp_up", b=p.get("up_b"),
                      tag=up_tag)
        h = jax.nn.gelu(h.astype(F32)).astype(x.dtype)
        flags.append(f1)
    out, f3 = dense(h, p["down"], ctx, "mlp_down", b=p.get("down_b"),
                    tag=down_tag)
    flags.append(f3)
    return out, _or(flags)


def _or(flags):
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_or(out, f)
    return out


def or_flags(*flags):
    return _or(list(flags))
