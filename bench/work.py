"""Operations and bytes of the protected GEMM sites, from the widths in
a configuration file (never from the program's own counters).

A site is what the program names in its ``abft[<scheme>][<site>]``
scopes.  Each entry is ``(k, n, gemms per layer, layers)``: one call of
the site on m token rows does ``2 m k n`` operations and moves
``(m k + k n + m n) * 2`` bytes per GEMM (bfloat16 operands and
output)."""

from __future__ import annotations

BYTES = 2  # bfloat16


def sites(cfg: dict) -> dict:
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


def site_work(cfg: dict, site: str, m: int) -> tuple:
    """(operations, bytes) of one call of ``site`` on ``m`` rows."""
    k, n, per_layer, layers = sites(cfg)[site]
    c = per_layer * layers
    return 2 * m * k * n * c, (m * k + k * n + m * n) * BYTES * c


def stack_flops_per_token(cfg: dict) -> int:
    """GEMM operations of one token through every layer (no head)."""
    return sum(2 * k * n * per * layers
               for s, (k, n, per, layers) in sites(cfg).items()
               if s != "lm_head")


def head_flops_per_token(cfg: dict) -> int:
    k, n, _, _ = sites(cfg)["lm_head"]
    return 2 * k * n


def window_work(cfg: dict, calls: list, peak_flops: float,
                bytes_per_s: float) -> dict:
    """Least time per site for a list of calls ``(kind, rows)``: kind is
    ``decode`` (m = rows through the stack and the head) or ``prefill``
    (m = rows through the stack, one row through the head).  Returns
    {site: seconds}, each call bounded by the larger of its operations
    over the peak and its bytes over the bandwidth."""
    out = {s: 0.0 for s in sites(cfg)}
    for kind, rows in calls:
        for s in out:
            m = rows if (s != "lm_head" or kind == "decode") else 1
            fl, by = site_work(cfg, s, m)
            out[s] += max(fl / peak_flops, by / bytes_per_s)
    return out
