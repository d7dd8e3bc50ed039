"""Plain reference of a dense pre-norm decoder (StableLM-2, Qwen3): the
published layer equations in ``jax.numpy``, float32 at HIGHEST matmul
precision, no kernels, no cache, no batching.  One sequence at a time,
one layer at a time, attention in blocks of queries, so that a long
sequence fits beside the weights.

Equations (Hugging Face ``StableLmForCausalLM`` and ``Qwen3ForCausalLM``):
  h = x + Attn(Norm(x));  y = h + W_down(silu(W_gate Norm(h)) * W_up Norm(h))
  Norm is LayerNorm with bias (StableLM) or RMSNorm (Qwen3); q and k get
  their per-head RMSNorm before rotary (Qwen3 ``qk_norm``); rotary acts
  on the first ``partial_rotary_factor`` of each head in the split-half
  (``rotate_half``) convention; q, k and v carry a bias when
  ``use_qkv_bias``; logits = W_head Norm(x_last).

``precision="fp8"`` is the control: every GEMM operand (weight and
activation) is rounded to float8 e4m3 with one scale per tensor, the
step below the bfloat16 the configuration states."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 512
BUCKET = 512
HEAD_BLOCK = 16384


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def _mm(x, w, low: bool):
    w = w.astype(F32)
    if low:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision=HI)


def _norm(x, w, b, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
            + b.astype(F32)
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, pos, frac: float, theta: float):
    hd = x.shape[-1]
    rot = int(hd * frac) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _eps(cfg):
    return float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps")))


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _layer(x, w, i, *, cfg_items, low):
    cfg = dict(cfg_items)
    T = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    kind, eps = cfg["norm"], _eps(cfg)
    lw = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
          for k, v in w.items()
          if k not in ("embed", "lm_head") and not k.startswith("final")}
    pos = jnp.arange(T)
    h = _norm(x, lw["attn_norm_w"], lw.get("attn_norm_b"), kind, eps)
    q, k, v = (_mm(h, lw[n], low) for n in ("wq", "wk", "wv"))
    if cfg.get("use_qkv_bias"):
        q, k, v = (q + lw["bq"].astype(F32), k + lw["bk"].astype(F32),
                   v + lw["bv"].astype(F32))
    q, k, v = (q.reshape(T, H, hd), k.reshape(T, KV, hd),
               v.reshape(T, KV, hd))
    if cfg.get("qk_norm"):
        q = _norm(q, lw["q_norm_w"], None, "rmsnorm", eps)
        k = _norm(k, lw["k_norm_w"], None, "rmsnorm", eps)
    frac, theta = cfg["partial_rotary_factor"], float(cfg["rope_theta"])
    q, k = _rope(q, pos, frac, theta), _rope(k, pos, frac, theta)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    def block(qs):                       # qs: query block start
        qb = jax.lax.dynamic_slice_in_dim(q, qs, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        mask = (qs + jnp.arange(Q_BLOCK))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    att = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))
    att = att.reshape(T, H * hd)
    x = x + _mm(att, lw["wo"], low)
    h = _norm(x, lw["mlp_norm_w"], lw.get("mlp_norm_b"), kind, eps)
    a = jax.nn.silu(_mm(h, lw["w_gate"], low)) * _mm(h, lw["w_up"], low)
    return x + _mm(a, lw["w_down"], low)


@functools.partial(jax.jit, static_argnames=("cfg_items", "low", "block"))
def _head(x, w, start, *, cfg_items, low, block):
    """Logits of one block of ``block`` vocabulary columns from
    ``start`` (the float32 copy of the whole head would not fit beside
    the weights); the fp8 control scales each block on its own."""
    cfg = dict(cfg_items)
    h = _norm(x, w["final_norm_w"], w.get("final_norm_b"), cfg["norm"],
              _eps(cfg))
    wb = jax.lax.dynamic_slice_in_dim(w["lm_head"], start, block, 1)
    return _mm(h, wb, low)


def logits(w: dict, cfg: dict, tokens, rows, precision: str = "f32"):
    """Logits (len(rows), vocab) in float32 at positions ``rows`` of the
    sequence ``tokens``; row t predicts token t + 1."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    low = precision == "fp8"
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    T = len(tokens)
    # causal, so padding at the end is inert; powers of two keep the
    # programs to compile few
    Tp = max(BUCKET, 1 << (T - 1).bit_length())
    toks = jnp.zeros((Tp,), jnp.int32).at[:T].set(jnp.asarray(tokens))
    x = w["embed"][toks].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, w, i, cfg_items=items, low=low)
    x = x[jnp.asarray(rows)]
    V = cfg["vocab_size"]
    block = min(HEAD_BLOCK, V)
    blocks = []
    for s in range(0, V, block):
        lo = min(s, V - block)           # the last block overlaps its left
        blk = _head(x, w, lo, cfg_items=items, low=low, block=block)
        blocks.append(blk[:, s - lo:])
    return jnp.concatenate(blocks, axis=1)
