"""Random weights from the seed, made by the benchmark on the device in
one jitted call, in bfloat16 (the type they are served in).

The layout is the benchmark's own: a flat dict of arrays stacked over
layers.  ``program.params`` nests the same arrays (no copy) into the
tree the serving engine takes; the plain reference reads this dict.

Scales keep every layer's output of order one, so the logits spread
over a few units and the greedy token is not a coin toss between
near-equal logits: GEMM weights N(0, 1/fan_in), embedding N(0, 1),
norm gains 1 + N(0, 0.1^2), norm and projection biases N(0, 0.25^2)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


def shapes(cfg: dict) -> dict:
    """{name: (shape, kind)}; kind picks the initializer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    ln = cfg["norm"] == "layernorm"
    out = {
        "embed": ((V, d), "embed"),
        "final_norm_w": ((d,), "gain"),
        "lm_head": ((d, V), "gemm"),
        "attn_norm_w": ((L, d), "gain"),
        "mlp_norm_w": ((L, d), "gain"),
        "wq": ((L, d, q), "gemm"),
        "wk": ((L, d, kv), "gemm"),
        "wv": ((L, d, kv), "gemm"),
        "wo": ((L, q, d), "gemm"),
        "w_gate": ((L, d, f), "gemm"),
        "w_up": ((L, d, f), "gemm"),
        "w_down": ((L, f, d), "gemm"),
    }
    if ln:
        out.update(final_norm_b=((d,), "bias"), attn_norm_b=((L, d), "bias"),
                   mlp_norm_b=((L, d), "bias"))
    if cfg.get("use_qkv_bias"):
        out.update(bq=((L, q), "bias"), bk=((L, kv), "bias"),
                   bv=((L, kv), "bias"))
    if cfg.get("qk_norm"):
        out.update(q_norm_w=((L, hd), "gain"), k_norm_w=((L, hd), "gain"))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, table):
    out = {}
    keys = jax.random.split(key, len(table))
    for k, (name, shape, kind) in zip(keys, table):
        z = jax.random.normal(k, shape, BF16)
        if kind == "gemm":
            z = z * BF16(1.0 / math.sqrt(shape[-2]))
        elif kind == "gain":
            z = BF16(1.0) + z * BF16(0.1)
        elif kind == "bias":
            z = z * BF16(0.25)
        out[name] = z
    return out


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``cfg`` for ``seed``, on the default device."""
    table = tuple((n, s, k) for n, (s, k) in sorted(shapes(cfg).items()))
    key = jax.random.key(seed % (1 << 32))
    key = jax.random.fold_in(key, (seed >> 32) % (1 << 31))
    return _make(key, table)
