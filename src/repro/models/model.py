"""Model assembly: builds every assigned architecture from the layer zoo.

Heterogeneous stacks (jamba's 1:7 mamba:attention interleave, deepseek's
3-dense + 58-MoE split, the VLM's every-5th cross-attention layer) are
expressed as a *segment plan*: the per-layer tag sequence is factored into
segments of repeating units, each segment scanned with stacked params so
HLO size stays bounded at 512-way SPMD (DESIGN.md §5).

Tags are "mixer:ffn:cross" with mixer in {attn, mla, mamba},
ffn in {dense, moe, none}, cross in {0, 1}.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.markers import coverage_scope
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models.layers import (
    LayerCtx,
    cache_layer,
    cache_write,
    dense,
    keep_storage_layout,
    mlp,
    norm,
    or_flags,
)

F32 = jnp.float32


def _init(key, shape, scale=0.02, dtype=jnp.bfloat16):
    return (scale * jax.random.normal(key, shape, F32)).astype(dtype)


# ---------------------------------------------------------------- plan

@dataclasses.dataclass(frozen=True)
class Segment:
    unit: tuple    # tags of one repeating unit
    repeats: int


def layer_tags(cfg: ModelConfig) -> list:
    tags = []
    for i in range(cfg.n_layers):
        mixer = cfg.layer_kind(i)            # attn | mamba
        if mixer == "attn" and cfg.attention == "mla":
            mixer = "mla"
        if cfg.d_ff or cfg.n_experts:
            ffn = cfg.ffn_kind(i)
        else:
            ffn = "none"
        cross = (
            "1"
            if cfg.cross_attn_every
            and i % cfg.cross_attn_every == cfg.cross_attn_every - 2
            else "0"
        )
        tags.append(f"{mixer}:{ffn}:{cross}")
    return tags


def seg_plan(cfg: ModelConfig) -> list:
    tags = layer_tags(cfg)
    n = len(tags)
    if n == 0:
        return []
    # (a) smallest period p such that the whole stack is p-periodic
    for p in range(1, min(12, n) + 1):
        if n % p == 0 and all(tags[i] == tags[i % p] for i in range(n)):
            return [Segment(unit=tuple(tags[:p]), repeats=n // p)]
    # (b) contiguous uniform runs (deepseek: 3 dense + 58 moe)
    segs = []
    start = 0
    for i in range(1, n + 1):
        if i == n or tags[i] != tags[start]:
            segs.append(Segment(unit=(tags[start],), repeats=i - start))
            start = i
    if len(segs) <= 4:
        return segs
    # (c) fallback: one unrolled segment
    return [Segment(unit=tuple(tags), repeats=1)]


# ---------------------------------------------------------------- layer init

def init_layer(cfg: ModelConfig, tag: str, key, dtype) -> dict:
    mixer, ffn, cross = tag.split(":")
    ks = jax.random.split(key, 4)
    norm_p = (
        lambda: {"w": jnp.ones((cfg.d_model,), dtype), "b": jnp.zeros(
            (cfg.d_model,), dtype)}
        if cfg.norm == "layernorm"
        else {"w": jnp.ones((cfg.d_model,), dtype)}
    )
    p: dict = {"mixer_norm": norm_p()}
    if mixer == "attn":
        p["mixer"] = attn.init_gqa(cfg, ks[0], dtype)
    elif mixer == "mla":
        p["mixer"] = attn.init_mla(cfg, ks[0], dtype)
    elif mixer == "mamba":
        p["mixer"] = mb.init_mamba(cfg, ks[0], dtype)
    if cross == "1":
        p["cross"] = attn.init_cross(cfg, ks[1], dtype)
        p["cross_norm"] = norm_p()
        p["cross_gate"] = jnp.zeros((), F32)
    if ffn == "dense":
        fk = jax.random.split(ks[2], 3)
        p["ffn"] = {
            "up": _init(fk[0], (cfg.d_model, cfg.d_ff), dtype=dtype),
            "gate": _init(fk[1], (cfg.d_model, cfg.d_ff), dtype=dtype),
            "down": _init(fk[2], (cfg.d_ff, cfg.d_model), dtype=dtype),
        }
        if cfg.act == "gelu":
            del p["ffn"]["gate"]
            p["ffn"]["up_b"] = jnp.zeros((cfg.d_ff,), dtype)
            p["ffn"]["down_b"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn_norm"] = norm_p()
    elif ffn == "moe":
        p["ffn"] = moe_mod.init_moe(cfg, ks[2], dtype)
        p["ffn_norm"] = norm_p()
    return p


def init_cross_cache(cfg: ModelConfig, batch: int, mem_len: int, dtype):
    """Cross-attention K/V cache: per-slot, fixed mem_len (encoder/vision
    memory never grows, so it is identical under dense and paged KV)."""
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, mem_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, mem_len, cfg.n_kv_heads, hd), dtype),
    }


def init_layer_cache(cfg: ModelConfig, tag: str, batch: int, max_len: int,
                     mem_len: int, dtype) -> dict:
    mixer, _, cross = tag.split(":")
    c: dict = {}
    if mixer == "attn":
        c["attn"] = attn.init_gqa_cache(cfg, batch, max_len, dtype)
    elif mixer == "mla":
        c["attn"] = attn.init_mla_cache(cfg, batch, max_len, dtype)
    elif mixer == "mamba":
        c["attn"] = mb.init_mamba_cache(cfg, batch, dtype)
    if cross == "1":
        c["cross"] = init_cross_cache(cfg, batch, mem_len, dtype)
    return c


# ---------------------------------------------------------------- layer apply

def apply_layer(
    x, lp, tag: str, cfg: ModelConfig, ctx: LayerCtx, positions,
    mode: str, cache, pos, mem, causal: bool = True,
    slots=None, lengths=None, tables=None, prefix_lens=None, layer=0,
):
    """One transformer/mamba layer.  mode: full | prefill | decode |
    verify.
    ``cache`` (every mode but full): this unit position's cache leaves,
    STACKED over the segment's repeats (R, ...), and ``layer`` the traced
    index of this layer's entry in them.  Each writer writes only the
    rows it produces into that entry and each reader reads the entry
    where it is consumed; the returned cache is the updated stack.
    ``pos`` (decode/verify): scalar or (B,) per-slot cursor vector.
    ``verify`` (speculative decoding): x is (B, T, D) — each row's last
    committed token plus its draft window — and ``lengths`` carries the
    per-row VALID window size (rows are padded to a uniform T);
    attention-only stacks, like chunked prefill, for the same reason
    (rollback resets a cursor, not an SSM recurrence).
    ``slots``/``lengths`` (prefill): scatter targets + ragged valid lengths
    for continuous-batching admission into an engine-deep cache.
    ``tables``: (B, W) block tables — selects the PAGED cache paths, where
    attention KV lives in a (num_blocks, block_size, ...) pool shared
    across slots (serve/paged_cache.py) while mamba state stays per-slot.
    ``prefix_lens``: (B,) logical start of each row's tokens — the
    prefix-sharing suffix prefill (paged) and the chunked-prefill resume
    path (paged or dense).  Attention layers only: SSM recurrence state
    is not a pure function of resident KV, so both are gated off for
    hybrid stacks at the engine.
    Returns (x, new_cache, flag, aux); ``new_cache`` holds only the
    entries this mode writes (cross-attention K/V is read-only in
    decode)."""
    mixer, ffn, cross = tag.split(":")
    flags = []
    aux = jnp.zeros((), F32)
    new_cache: dict = {}

    h = norm(x, lp["mixer_norm"], cfg.norm, cfg.norm_eps)
    if mixer in ("attn", "mla"):
        fwd = attn.gqa_forward if mixer == "attn" else attn.mla_forward
        if tables is not None:
            pre = (attn.gqa_paged_prefill if mixer == "attn"
                   else attn.mla_paged_prefill)
            dec = (attn.gqa_paged_decode if mixer == "attn"
                   else attn.mla_paged_decode)
        else:
            pre = attn.gqa_prefill if mixer == "attn" else attn.mla_prefill
            dec = attn.gqa_decode if mixer == "attn" else attn.mla_decode
        if mode == "full":
            if mixer == "attn":
                a, f = fwd(h, lp["mixer"], cfg, ctx, positions, causal=causal)
            else:
                a, f = fwd(h, lp["mixer"], cfg, ctx, positions)
        elif mode == "prefill":
            if tables is not None:
                a, nc, f = pre(h, lp["mixer"], cfg, ctx, positions,
                               cache["attn"], layer, tables, lengths,
                               starts=prefix_lens)
            else:
                a, nc, f = pre(h, lp["mixer"], cfg, ctx, positions,
                               cache["attn"], layer, slots=slots,
                               lengths=lengths, starts=prefix_lens)
            new_cache["attn"] = nc
        elif mode == "verify":
            ver = (attn.gqa_verify if mixer == "attn" else attn.mla_verify)
            if tables is not None:
                ver = (attn.gqa_paged_verify if mixer == "attn"
                       else attn.mla_paged_verify)
                a, nc, f = ver(h, lp["mixer"], cfg, ctx, pos,
                               cache["attn"], layer, lengths, tables)
            else:
                a, nc, f = ver(h, lp["mixer"], cfg, ctx, pos,
                               cache["attn"], layer, lengths)
            new_cache["attn"] = nc
        else:
            if tables is not None:
                a, nc, f = dec(h, lp["mixer"], cfg, ctx, pos, cache["attn"],
                               layer, tables)
            else:
                a, nc, f = dec(h, lp["mixer"], cfg, ctx, pos, cache["attn"],
                               layer)
            new_cache["attn"] = nc
    else:
        # mamba state is constant-size per request (conv window + SSD
        # state) — one implicit permanently-resident block per slot, so
        # the paged engine uses the same per-slot paths and the block
        # tables are simply not forwarded
        assert prefix_lens is None, (
            "prefix sharing / chunked prefill cannot resume the SSM "
            "recurrence state mid-prompt")
        assert mode != "verify", (
            "speculative verify cannot roll the SSM recurrence state "
            "back to the last accepted position")
        if mode == "full":
            a, f = mb.mamba_forward(h, lp["mixer"], cfg, ctx)
        elif mode == "prefill":
            a, nc, f = mb.mamba_prefill(h, lp["mixer"], cfg, ctx,
                                        cache["attn"], layer,
                                        slots=slots, lengths=lengths)
            new_cache["attn"] = nc
        else:
            a, nc, f = mb.mamba_decode(h, lp["mixer"], cfg, ctx,
                                       cache["attn"], layer)
            new_cache["attn"] = nc
    x = x + a
    flags.append(f)

    if cross == "1":
        h = norm(x, lp["cross_norm"], cfg.norm, cfg.norm_eps)
        if mode == "decode":
            ck = cache_layer(cache["cross"]["k"], layer)
            cv = cache_layer(cache["cross"]["v"], layer)
            fkv = jnp.zeros((), bool)
        else:
            ck, cv, fkv = attn.cross_kv(mem, lp["cross"], cfg, ctx)
            if mode == "prefill":
                if slots is None:
                    new_cache["cross"] = {
                        n: cache_write(cache["cross"][n], layer, new)
                        for n, new in (("k", ck), ("v", cv))}
                else:
                    new_cache["cross"] = {
                        n: cache["cross"][n].at[layer, slots].set(
                            new.astype(cache["cross"][n].dtype))
                        for n, new in (("k", ck), ("v", cv))}
        a, f = attn.cross_forward(h, ck, cv, lp["cross"], cfg, ctx)
        gate = jnp.tanh(lp["cross_gate"]).astype(x.dtype)
        x = x + gate * a
        flags += [fkv, f]

    if ffn != "none":
        h = norm(x, lp["ffn_norm"], cfg.norm, cfg.norm_eps)
        if ffn == "moe":
            o, f, a_loss = moe_mod.moe_forward(h, lp["ffn"], cfg, ctx)
            aux = aux + a_loss
        else:
            o, f = mlp(h, lp["ffn"], ctx, act=cfg.act)
        x = x + o
        flags.append(f)

    return x, new_cache, or_flags(*flags), aux


# ---------------------------------------------------------------- stacks

def _read_only(mode: str) -> tuple:
    """Layer-cache entries ``mode`` only reads: they stay out of the layer
    scan's carry, so they are neither written nor copied."""
    return ("cross",) if mode == "decode" else ()


def run_stack(
    x, segments_params, plan, cfg: ModelConfig, ctx: LayerCtx, positions,
    mode: str, caches, pos, mem, causal: bool = True, remat: bool = False,
    layer_offset: int = 0, slots=None, lengths=None, tables=None,
    prefix_lens=None,
):
    """Apply all segments.  caches: list aligned with plan (or None).
    ``pos``: decode cursor — scalar or (B,) vector; ``slots``/``lengths``
    thread the continuous-batching prefill path, ``tables`` the paged
    block-table path, and ``prefix_lens`` the prefix-sharing suffix
    prefill (see apply_layer).

    Each segment is one ``lax.scan`` over its stacked params (a
    single-unit segment runs the same body once, at index 0).  The
    segment's stacked cache leaves (R, ...) travel as part of the scan's
    CARRY, never as its xs/ys: layer ``rep`` writes only its new rows
    into entry ``rep`` of the carried buffers, which XLA updates in
    place, and reads that entry where attention consumes it.  Entries a
    mode only reads (``_read_only``) are closed over instead.
    Returns (x, new_caches, flag, aux)."""
    flag = jnp.zeros((), bool)
    aux = jnp.zeros((), F32)
    ro_names = _read_only(mode)
    new_caches = []
    offset = layer_offset
    for si, seg in enumerate(plan):
        sp = segments_params[si]
        p = len(seg.unit)
        seg_off = offset
        if caches is None:
            written, ro = None, None
        else:
            written = {q: {n: c for n, c in lc.items() if n not in ro_names}
                       for q, lc in caches[si].items()}
            ro = {q: {n: c for n, c in lc.items() if n in ro_names}
                  for q, lc in caches[si].items()}

        def unit_body(carry, xs, _unit=seg.unit, _off=seg_off, _p=p,
                      _ro=ro):
            xx, fl, au, uc = carry
            uc = keep_storage_layout(uc, ctx.cache_device)
            up, rep = xs
            for q, tag in enumerate(_unit):
                idx = _off + rep * _p + q
                lctx = ctx.with_layer(jnp.asarray(idx, jnp.int32))
                key = f"pos{q}"
                lc = None if uc is None else {**uc[key], **_ro[key]}
                xx, ncq, f, a = apply_layer(
                    xx, up[key], tag, cfg, lctx, positions, mode, lc, pos,
                    mem, causal=causal, slots=slots, lengths=lengths,
                    tables=tables, prefix_lens=prefix_lens, layer=rep,
                )
                if uc is not None:
                    uc = {**uc, key: ncq}
                fl = jnp.logical_or(fl, f)
                au = au + a
            return ((xx, fl, au, keep_storage_layout(uc, ctx.cache_device)),
                    None)

        body = jax.checkpoint(unit_body) if remat else unit_body
        carry = (x, flag, aux, written)
        if seg.repeats == 1:
            # single unit: apply directly (no scan) at index 0
            sp1 = jax.tree_util.tree_map(lambda a: a[0], sp)
            carry, _ = body(carry, (sp1, jnp.zeros((), jnp.int32)))
        else:
            reps = jnp.arange(seg.repeats, dtype=jnp.int32)
            carry, _ = jax.lax.scan(body, carry, (sp, reps))
        x, flag, aux, written = carry
        if caches is not None:
            new_caches.append({q: {**written[q], **ro[q]} for q in written})
        offset += p * seg.repeats
    return x, new_caches, flag, aux


# ---------------------------------------------------------------- model

class ForwardOut(NamedTuple):
    logits: jnp.ndarray
    flag: jnp.ndarray
    aux_loss: jnp.ndarray
    mtp_logits: Any = None


def sinusoid_pos(positions, d_model: int):
    """Whisper-style sinusoidal position encoding.  positions: (B, L)."""
    half = d_model // 2
    freqs = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(half, dtype=F32) / max(half - 1, 1))
    ang = positions.astype(F32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class Model:
    """Functional model wrapper for one architecture."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = seg_plan(cfg)
        self.enc_plan = (
            [Segment(unit=("attn:dense:0",), repeats=cfg.n_enc_layers)]
            if cfg.is_encoder_decoder else []
        )

    # -------------------------------------------------- init
    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.cfg
        k_emb, k_seg, k_enc, k_head, k_misc = jax.random.split(key, 5)
        params: dict = {
            "embed": _init(k_emb, (cfg.vocab_size, cfg.d_model), dtype=dtype),
            "final_norm": (
                {"w": jnp.ones((cfg.d_model,), dtype),
                 "b": jnp.zeros((cfg.d_model,), dtype)}
                if cfg.norm == "layernorm"
                else {"w": jnp.ones((cfg.d_model,), dtype)}
            ),
            "segments": self._init_segments(self.plan, k_seg, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = _init(
                k_head, (cfg.d_model, cfg.vocab_size), dtype=dtype)
        if cfg.is_encoder_decoder:
            params["encoder"] = {
                "segments": self._init_segments(self.enc_plan, k_enc, dtype),
                "final_norm": {
                    "w": jnp.ones((cfg.d_model,), dtype),
                    "b": jnp.zeros((cfg.d_model,), dtype)},
            }
        if cfg.is_encoder_decoder and cfg.n_mels:
            ck = jax.random.split(k_misc, 2)
            params["conv_stem"] = {
                "w1": _init(ck[0], (3, cfg.n_mels, cfg.d_model),
                            dtype=dtype),
                "b1": jnp.zeros((cfg.d_model,), dtype),
                "w2": _init(ck[1], (3, cfg.d_model, cfg.d_model),
                            dtype=dtype),
                "b2": jnp.zeros((cfg.d_model,), dtype),
            }
        if cfg.vision_dim:
            params["vision_proj"] = _init(
                k_misc, (cfg.vision_dim, cfg.d_model), dtype=dtype)
        if cfg.mtp_depth:
            mk = jax.random.split(k_misc, 3)
            params["mtp"] = {
                "proj": _init(mk[0], (2 * cfg.d_model, cfg.d_model),
                              dtype=dtype),
                "layer": init_layer(
                    cfg, layer_tags(cfg)[-1], mk[1], dtype),
                "norm": {"w": jnp.ones((cfg.d_model,), dtype)},
            }
        return params

    def _init_segments(self, plan, key, dtype):
        cfg = self.cfg
        segs = []
        keys = jax.random.split(key, max(len(plan), 1))
        for seg, k in zip(plan, keys):
            rkeys = jax.random.split(k, seg.repeats)

            def one(kk, _unit=seg.unit):
                uks = jax.random.split(kk, len(_unit))
                return {
                    f"pos{q}": init_layer(cfg, tag, uks[q], dtype)
                    for q, tag in enumerate(_unit)
                }

            segs.append(jax.vmap(one)(rkeys))
        return segs

    # -------------------------------------------------- cache
    def _resolved_mem_len(self, mem_len: int | None) -> int:
        cfg = self.cfg
        return mem_len or (
            cfg.enc_seq_len if cfg.is_encoder_decoder else cfg.n_image_tokens)

    def _stack_caches(self, layer_cache_fn):
        """Build the per-segment cache list: one layer-cache per unit
        position, stacked over segment repeats."""
        caches = []
        for seg in self.plan:
            one = {f"pos{q}": layer_cache_fn(tag)
                   for q, tag in enumerate(seg.unit)}
            caches.append(
                jax.tree_util.tree_map(
                    lambda a, _r=seg.repeats: jnp.broadcast_to(
                        a[None], (_r,) + a.shape), one))
        return caches

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   mem_len: int | None = None):
        cfg = self.cfg
        mem_len = self._resolved_mem_len(mem_len)
        return self._stack_caches(
            lambda tag: init_layer_cache(
                cfg, tag, batch, max_len, mem_len, dtype))

    def init_paged_cache(self, slots: int, num_blocks: int,
                         block_size: int, dtype=jnp.bfloat16,
                         mem_len: int | None = None):
        """Paged-engine cache: attention KV lives in per-layer
        (num_blocks, block_size, ...) pools indexed by the engine's
        shared block tables (serve/paged_cache.py); mamba and cross-attn
        state stay per-slot (constant-size / fixed mem_len)."""
        from repro.serve import paged_cache as pc

        cfg = self.cfg
        mem_len = self._resolved_mem_len(mem_len)

        def one_layer(tag):
            mixer, _, cross = tag.split(":")
            c: dict = {}
            if mixer == "attn":
                c["attn"] = pc.init_paged_gqa_cache(
                    cfg, num_blocks, block_size, dtype)
            elif mixer == "mla":
                c["attn"] = pc.init_paged_mla_cache(
                    cfg, num_blocks, block_size, dtype)
            elif mixer == "mamba":
                c["attn"] = pc.init_paged_mamba_cache(cfg, slots, dtype)
            if cross == "1":
                c["cross"] = init_cross_cache(cfg, slots, mem_len, dtype)
            return c

        return self._stack_caches(one_layer)

    # -------------------------------------------------- memory (enc / vision)
    def _conv_stem(self, params, audio):
        """Whisper audio frontend: two width-3 1-D convs (stride 1 then 2)
        with GELU, mapping (B, T, n_mels) log-mel frames to
        (B, ceil(T/2), d_model).

        flops[conv_stem]: conv FLOPs have no registered ABFT scheme —
        the coverage auditor reports them as the known_unprotected conv
        frontend (ROADMAP item 5a tracks closing the gap with a
        checksummed im2col GEMM)."""
        cs = params["conv_stem"]
        with coverage_scope("conv_stem"):
            h = jax.lax.conv_general_dilated(
                audio.astype(cs["w1"].dtype), cs["w1"],
                window_strides=(1,), padding="SAME",
                dimension_numbers=("NWC", "WIO", "NWC"))
            h = jax.nn.gelu(h + cs["b1"])
            h = jax.lax.conv_general_dilated(
                h, cs["w2"], window_strides=(2,), padding="SAME",
                dimension_numbers=("NWC", "WIO", "NWC"))
            h = jax.nn.gelu(h + cs["b2"])
        return h

    def _memory(self, params, batch, ctx):
        """Encoder output (whisper) or projected vision tokens (vlm)."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            if "audio" in batch and "conv_stem" in params:
                # (B, T, n_mels) raw log-mel frames through the conv stem
                frames = self._conv_stem(params, batch["audio"])
            else:
                frames = batch["enc_input"]      # (B, S_enc, d_model) stub
            B, S, _ = frames.shape
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            h = frames + sinusoid_pos(pos, cfg.d_model).astype(frames.dtype)
            # encoder sites get their own plan/audit namespace ("enc.")
            enc_ctx = dataclasses.replace(ctx, site_prefix="enc.")
            h, _, flag, _ = run_stack(
                h, params["encoder"]["segments"], self.enc_plan, cfg,
                enc_ctx, pos, "full", None, None, None, causal=False)
            h = norm(h, params["encoder"]["final_norm"], "layernorm",
                     cfg.norm_eps)
            return h, flag
        if cfg.vision_dim:
            img = batch["images"]                # (B, n_img, vision_dim)
            mem, f = dense(img, params["vision_proj"], ctx, "cross_qkv",
                           tag="vision.proj")
            return mem, f
        return None, jnp.zeros((), bool)

    # -------------------------------------------------- forward (train)
    def forward(self, params, batch, ctx: LayerCtx) -> ForwardOut:
        cfg = self.cfg
        tokens = batch["tokens"]                 # (B, L)
        B, L = tokens.shape
        mem, mem_flag = self._memory(params, batch, ctx)
        x = params["embed"][tokens]
        positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
        if cfg.is_encoder_decoder:
            x = x + sinusoid_pos(positions, cfg.d_model).astype(x.dtype)
        x, _, flag, aux = run_stack(
            x, params["segments"], self.plan, cfg, ctx, positions,
            "full", None, None, mem, remat=True)
        x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits, f_head = self._head(params, x, ctx)
        flag = or_flags(flag, f_head, mem_flag)

        mtp_logits = None
        if cfg.mtp_depth and "mtp" in params:
            mtp_logits, f_mtp = self._mtp(params, x, tokens, ctx, positions)
            flag = or_flags(flag, f_mtp)
        return ForwardOut(
            logits=logits, flag=flag, aux_loss=aux, mtp_logits=mtp_logits)

    def _head(self, params, x, ctx):
        cfg = self.cfg
        w = (
            params["embed"].T.astype(x.dtype)
            if cfg.tie_embeddings else params["lm_head"]
        )
        return dense(x, w, ctx, "lm_head", out_dtype=jnp.float32)

    def _mtp(self, params, h, tokens, ctx, positions):
        """DeepSeek-V3 multi-token prediction head (depth 1)."""
        cfg = self.cfg
        emb_next = params["embed"][jnp.roll(tokens, -1, axis=1)]
        comb = jnp.concatenate(
            [norm(h, params["mtp"]["norm"], "rmsnorm", cfg.norm_eps),
             emb_next], axis=-1)
        hm, f1 = dense(comb, params["mtp"]["proj"], ctx, "mlp_up",
                       tag="mtp.proj")
        hm, _, f2, _ = apply_layer(
            hm, params["mtp"]["layer"], layer_tags(cfg)[-1], cfg, ctx,
            positions, "full", None, None, None)
        logits, f3 = self._head(params, hm, ctx)
        return logits, or_flags(f1, f2, f3)

    # -------------------------------------------------- prefix sharing
    @property
    def supports_prefix_sharing(self) -> bool:
        """Prefix KV sharing is sound only when a token's cached state is
        a pure function of the token prefix: SSM layers carry recurrent
        state outside the block pool, and encoder-decoder / vision stacks
        condition every position on per-request memory, so identical
        prompt tokens do NOT imply identical cache content there."""
        cfg = self.cfg
        if cfg.is_encoder_decoder or cfg.vision_dim or cfg.cross_attn_every:
            return False
        return not any(t.startswith("mamba") for t in layer_tags(cfg))

    @property
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill resumes a prompt mid-sequence from resident
        cache state.  Attention can: KV at positions < start is exactly
        what a later chunk needs.  SSM layers cannot — the recurrence
        state after ``start`` tokens is not re-enterable through the
        prefill path — and encoder-decoder / vision stacks would redo
        their per-request memory every chunk, so both are gated off.
        (Same condition as prefix sharing, for the same underlying
        reason: resident state must be a pure, resumable function of the
        token prefix.)"""
        return self.supports_prefix_sharing

    def protection_plan(self, hw=None, policy=None, *,
                        phase: str = "serve", n_tokens: int = 1,
                        dtype_bytes: int = 2, model_parallel: int = 1):
        """Compile this model's ProtectionPlan (core/policy.py): per-site
        intensity-guided selections with the explicit first-layer flag,
        plus the serving fast paths (``for_step``, ``tune_chunk_budget``)
        the engine consults.  ``n_tokens`` sets the representative GEMM M
        dim (batch*seq for full passes; batch/slots for decode);
        ``model_parallel=k`` compiles one shard's post-sharding shapes
        (the per-device plan on a k-wide model axis)."""
        from repro.core.hardware import device_hardware
        from repro.core.policy import ProtectionPlan

        return ProtectionPlan.for_model(
            self.cfg, hw=hw or device_hardware(), policy=policy, phase=phase,
            n_tokens=n_tokens, dtype_bytes=dtype_bytes,
            model_parallel=model_parallel)

    def audit_coverage(self, phase: str = "mixed", **kw):
        """Static protection-coverage audit (repro.analysis): trace this
        model's prefill/decode to jaxprs, walk every FLOP-carrying
        primitive, and classify each as protected / allowlisted /
        known-unprotected / UNPROTECTED.  Returns an ``AuditReport``."""
        from repro.analysis.audit import audit_model

        return audit_model(self, phase=phase, **kw)

    def copy_paged_blocks(self, cache, src, dst):
        """Functional device copy ``pool[dst[i]] <- pool[src[i]]`` on
        every paged attention leaf — the COW payload move.  Walks the
        segment plan so per-slot leaves (mamba state, cross KV) are never
        touched even if their leading dims collide with the pool's."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        out = []
        for seg, segc in zip(self.plan, cache):
            nc = {}
            for q, tag in enumerate(seg.unit):
                mixer = tag.split(":")[0]
                lc = dict(segc[f"pos{q}"])
                if mixer in ("attn", "mla"):
                    lc["attn"] = {
                        k: leaf.at[:, dst].set(leaf[:, src])
                        for k, leaf in lc["attn"].items()
                    }
                nc[f"pos{q}"] = lc
            out.append(nc)
        return out

    # -------------------------------------------------- prefill / decode
    def prefill(self, params, batch, cache, ctx: LayerCtx,
                slots=None, lengths=None, block_tables=None,
                prefix_lens=None):
        """Prefill the cache from ``batch["tokens"]`` (B, L).

        Default path: cache is B-deep, rows map 1:1 to the batch, logits
        come from the last token of each row.

        Continuous-batching path (``slots``/``lengths`` given): cache is
        engine-deep, tokens are an admission batch padded to a common L,
        ``slots`` (A,) names the cache rows to fill and ``lengths`` (A,)
        the true prompt lengths.  Attention/SSM recurrences are masked at
        the per-row length and logits are gathered at the last *valid*
        token of each row.

        Paged path (``block_tables`` (A, W) additionally given): the
        cache is a block pool (init_paged_cache) and attention KV
        scatters via the tables instead of dense rows.

        Mid-sequence path (``prefix_lens`` (A,) additionally given, paged
        OR dense): tokens hold only each row's tail — the unshared suffix
        under prefix sharing, or one resumable chunk under the chunked-
        prefill scheduler — and ``lengths`` its valid token count; row a's
        first token sits at logical position ``prefix_lens[a]`` (0 for
        rows starting from scratch).  Rotary offsets, causal masks, and
        cache scatter targets are all computed from the true logical
        position — the prefix KV already resident in the cache is what
        the tail attends to."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, L = tokens.shape
        mem, mem_flag = self._memory(params, batch, ctx)
        x = params["embed"][tokens]
        positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
        if prefix_lens is not None:
            positions = prefix_lens[:, None].astype(jnp.int32) + positions
        if cfg.is_encoder_decoder:
            x = x + sinusoid_pos(positions, cfg.d_model).astype(x.dtype)
        x, new_cache, flag, _ = run_stack(
            x, params["segments"], self.plan, cfg, ctx, positions,
            "prefill", cache, None, mem, slots=slots, lengths=lengths,
            tables=block_tables, prefix_lens=prefix_lens)
        x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        if lengths is not None:
            last = x[jnp.arange(B), jnp.maximum(lengths - 1, 0)][:, None]
        else:
            last = x[:, -1:, :]
        logits, f_head = self._head(params, last, ctx)
        return logits, new_cache, or_flags(flag, f_head, mem_flag)

    def decode(self, params, token, cache, pos, ctx: LayerCtx,
               block_tables=None):
        """token: (B, 1) int32; pos: scalar int32 OR (B,) int32 per-slot
        position vector.  With a vector, each batch row writes its new KV
        at its own cursor and attends its own prefix — the contract the
        continuous-batching engine relies on for mixed-length traffic.
        ``block_tables`` (B, W): paged cache — each row's KV entry lands
        at ``tables[b, pos[b] // block_size]`` in the block pool."""
        cfg = self.cfg
        B = token.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        x = params["embed"][token]
        if cfg.is_encoder_decoder:
            positions = pos[:, None]
            x = x + sinusoid_pos(positions, cfg.d_model).astype(x.dtype)
        x, new_cache, flag, _ = run_stack(
            x, params["segments"], self.plan, cfg, ctx, None,
            "decode", cache, pos, None, tables=block_tables)
        x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits, f_head = self._head(params, x, ctx)
        return logits, new_cache, or_flags(flag, f_head)

    def verify(self, params, tokens, cache, pos, ctx: LayerCtx, valid,
               block_tables=None):
        """Speculative-decoding batched verify: score K+1 positions per
        slot in ONE call.  tokens: (B, T) int32 — row b holds its last
        committed token followed by its (padded) draft window; pos: (B,)
        per-slot cursors; ``valid`` (B,) the per-row usable window size
        (``K_slot + 1``; padded rows beyond it neither write cache nor
        emit — their logits are discarded host-side).  Row b's token t
        sits at logical position ``pos[b] + t``; its k/v land at that
        cache position and logits[b, t] predicts position
        ``pos[b] + t + 1``.  Returns ALL T logits (B, T, V) — the host
        acceptance loop compares them against the drafts.  Attention-
        only stacks (supports_chunked_prefill); rejected-draft KV above
        the accepted cursor is dead weight — masked by per-query lengths
        and overwritten before any later query can attend it."""
        cfg = self.cfg
        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        x = params["embed"][tokens]
        x, new_cache, flag, _ = run_stack(
            x, params["segments"], self.plan, cfg, ctx, None,
            "verify", cache, pos, None, lengths=valid,
            tables=block_tables)
        x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits, f_head = self._head(params, x, ctx)
        return logits, new_cache, or_flags(flag, f_head)


@functools.lru_cache(maxsize=64)
def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
