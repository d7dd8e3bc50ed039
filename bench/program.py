"""The system under test, as ``launch/serve.py`` builds it.

This is the one module of the benchmark that imports the program
(``src/repro``): the engine with the intensity-guided policy, bfloat16
on a TPU, the compiled Pallas kernels, a dense cache and a fixed
chunked-prefill budget.  The benchmark drives it only through
``ServeEngine.admit`` / ``step`` and reads ``Request`` stamps and
``EngineStats`` counters."""

from __future__ import annotations

import sys

import jax

from bench.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` for a benchmark configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["architecture"] != "dense_decoder" or cfg["hidden_act"] != "silu":
        raise ValueError(f"{name}: no mapping for this architecture")
    eps = cfg.get("layer_norm_eps", cfg.get("rms_norm_eps"))
    return ModelConfig(
        name=name, family="dense", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm=cfg["norm"], norm_eps=float(eps), act="silu",
        qk_norm=bool(cfg.get("qk_norm")),
        qkv_bias=bool(cfg.get("use_qkv_bias")),
        rope_theta=float(cfg["rope_theta"]),
        rope_pct=float(cfg["partial_rotary_factor"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


def params(model, w: dict) -> dict:
    """Nest the benchmark's weights (bench/weights.py) into the engine's
    parameter tree, sharing the arrays, and check every leaf's shape and
    type against the tree the program itself would build."""
    cfg = model.cfg

    def norm(prefix):
        p = {"w": w[f"{prefix}_w"]}
        if cfg.norm == "layernorm":
            p["b"] = w[f"{prefix}_b"]
        return p

    mixer = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if cfg.qkv_bias:
        mixer.update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    if cfg.qk_norm:
        mixer.update(q_norm=w["q_norm_w"], k_norm=w["k_norm_w"])
    layer = {"mixer_norm": norm("attn_norm"), "mixer": mixer,
             "ffn": {"up": w["w_up"], "gate": w["w_gate"],
                     "down": w["w_down"]},
             "ffn_norm": norm("mlp_norm")}
    tree = {"embed": w["embed"], "final_norm": norm("final_norm"),
            "segments": [{"pos0": layer}], "lm_head": w["lm_head"]}
    want = jax.eval_shape(
        lambda k: model.init_params(k, dtype=w["embed"].dtype),
        jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree_util.tree_structure(got) != \
            jax.tree_util.tree_structure(want) or \
            jax.tree_util.tree_leaves(got) != \
            jax.tree_util.tree_leaves(want):
        raise ValueError("benchmark weights do not match the engine's "
                         "parameter tree")
    return tree


def build(cfg: dict, name: str, settings: dict, w: dict, seed: int):
    """(engine, Request class, EngineStats class) for one cell."""
    from repro.launch.serve import abft_config, serving_dtype
    from repro.models import build_model
    from repro.serve.engine import (EngineStats, RecoveryPolicy, Request,
                                    ServeEngine)

    model = build_model(model_config(cfg, name))
    engine = ServeEngine(
        model, params(model, w), slots=settings["slots"],
        max_len=settings["max_len"], abft=abft_config("auto"),
        dtype=serving_dtype(), policy=RecoveryPolicy(max_retries=1),
        cache_kind="dense", chunk_tokens=settings["chunk_tokens"],
        seed=seed % (1 << 31))
    return engine, Request, EngineStats
