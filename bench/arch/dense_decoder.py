"""A dense pre-norm decoder (StableLM-2, Qwen3) as the benchmark lays it
out.

The weights are a flat dict of arrays stacked over layers
(``shapes``); ``params`` nests the same arrays, without a copy, into the
tree the serving engine takes.  On a ``model`` mesh axis the attention
and MLP projections are split the Megatron way (``SPLIT``): q, k, v, up
and gate by columns, o and down by rows, the head and the embedding by
vocabulary rows; norms are whole on every chip."""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

# each protected GEMM site and how a model axis splits it: "column" and
# "vocab" divide n, "row" divides k
SPLIT = {"attn.q": "column", "attn.k": "column", "attn.v": "column",
         "attn.o": "row", "mlp.up": "column", "mlp.down": "row",
         "lm_head": "vocab"}

# the flat weights that a model axis splits, and the axis of each (from
# the end); every other weight is whole on every chip
_SHARDED = {"wq": -1, "wk": -1, "wv": -1, "bq": -1, "bk": -1, "bv": -1,
            "w_gate": -1, "w_up": -1, "lm_head": -1,
            "wo": -2, "w_down": -2, "embed": -2}


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` for a benchmark configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{name}: only gated-silu MLPs are mapped")
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
    """Nest the benchmark's weights into the engine's parameter tree,
    sharing the arrays, and check every leaf's shape and type against
    the tree the program itself would build."""
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


def shapes(cfg: dict) -> dict:
    """{name: (shape, kind)}; kind picks the initializer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
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
    if cfg["norm"] == "layernorm":
        out.update(final_norm_b=((d,), "bias"), attn_norm_b=((L, d), "bias"),
                   mlp_norm_b=((L, d), "bias"))
    if cfg.get("use_qkv_bias"):
        out.update(bq=((L, q), "bias"), bk=((L, kv), "bias"),
                   bv=((L, kv), "bias"))
    if cfg.get("qk_norm"):
        out.update(q_norm_w=((L, hd), "gain"), k_norm_w=((L, hd), "gain"))
    return out


def specs(cfg: dict, chips: int) -> dict:
    """{name: PartitionSpec} of each flat weight on a ``model`` axis of
    ``chips`` devices; a dimension that ``chips`` does not divide stays
    whole."""
    out = {}
    for name, (shape, _) in shapes(cfg).items():
        axes = [None] * len(shape)
        ax = _SHARDED.get(name)
        if ax is not None and shape[ax] % chips == 0:
            axes[ax] = "model"
        out[name] = P(*axes)
    return out


def sites(cfg: dict) -> dict:
    """{site: (k, n, gemms per layer, layers)} at full width, named as
    the program's ``abft[<scheme>][<site>]`` scopes name them."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    if cfg.get("hidden_act") != "silu":
        raise ValueError("only gated-silu MLPs are counted")
    return {
        "attn.q": (d, q, 1, L),
        "attn.k": (d, kv, 1, L),
        "attn.v": (d, kv, 1, L),
        "attn.o": (q, d, 1, L),
        "mlp.up": (d, f, 2, L),          # up and gate share the site name
        "mlp.down": (f, d, 1, L),
        "lm_head": (d, cfg["vocab_size"], 1, 1),
    }
