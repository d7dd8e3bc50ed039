"""Operations and bytes of the protected GEMM sites, from the widths in
a configuration file and the cell's chips (never from the program's own
counters).

A site is what the program names in its ``abft[<scheme>][<site>]``
scopes; the architecture module (``bench/arch/<architecture>.py``)
lists them as ``(k, n, gemms per layer, layers)`` and says how a model
axis splits each (``SPLIT``).  One call of the site on m token rows
does ``2 m k n`` operations and moves ``(m k + k n + m n) * 2`` bytes per
GEMM (bfloat16 operands and output); on ``chips`` devices each device
does its shard's: ``n / chips`` for a column- or vocabulary-parallel
site, ``k / chips`` for a row-parallel one."""

from __future__ import annotations

from bench import spec

BYTES = 2  # bfloat16


def sites(cfg: dict) -> dict:
    """{site: (k, n, gemms per layer, layers)} of the whole model."""
    return spec.arch(cfg["architecture"]).sites(cfg)


def device_sites(cfg: dict, chips: int) -> dict:
    """``sites`` with each GEMM cut to one device's shard on a ``model``
    axis of ``chips`` (a width that ``chips`` does not divide stays
    whole, as the program's sharding rules leave it)."""
    split = spec.arch(cfg["architecture"]).SPLIT
    out = {}
    for s, (k, n, per, layers) in sites(cfg).items():
        if split[s] in ("column", "vocab") and n % chips == 0:
            n //= chips
        elif split[s] == "row" and k % chips == 0:
            k //= chips
        out[s] = (k, n, per, layers)
    return out


def site_work(cfg: dict, site: str, m: int, chips: int = 1) -> tuple:
    """(operations, bytes) of one call of ``site`` on ``m`` rows, on one
    of ``chips`` devices."""
    k, n, per_layer, layers = device_sites(cfg, chips)[site]
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
                bytes_per_s: float, chips: int = 1) -> dict:
    """Least time per site on one of ``chips`` devices for a list of
    calls ``(kind, rows)``: kind is ``decode`` (m = rows through the
    stack and the head) or ``prefill`` (m = rows through the stack, one
    row through the head).  Returns {site: seconds}, each call bounded by
    the larger of its operations over one device's peak and its bytes
    over its bandwidth."""
    out = {s: 0.0 for s in sites(cfg)}
    for kind, rows in calls:
        for s in out:
            m = rows if (s != "lm_head" or kind == "decode") else 1
            fl, by = site_work(cfg, s, m, chips)
            out[s] += max(fl / peak_flops, by / bytes_per_s)
    return out
