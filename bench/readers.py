"""Per-layer readers shared by the files in ``bench/metrics/``.  Each
takes the run context that ``harness.traced`` builds and returns a
number, or None where the run holds nothing to read (a share of a
roofline or of a peak is then left out, never reported as 0).

The context holds the ops of each of the cell's devices (``devices``)
and, as ``ops``, those of the device with the most idle time."""

from __future__ import annotations

from bench import stats, trace, work


def idle_share(run) -> float:
    """Per cent of the traced window in which no operation ran on the
    device, on the cell's device with the most idle time."""
    w = run.window_s * 1e9
    return 100.0 * max(1.0 - trace.busy_ns(ops, w) / w
                       for ops in run.devices.values())


def collective_share(run) -> float | None:
    """Per cent of a device's busy time in which an exchange between
    chips runs and no other op does, on the device where that share is
    largest; None where no device ran a collective."""
    w = run.window_s * 1e9
    shares = []
    for ops in run.devices.values():
        coll = trace.collective_ns(ops, w)
        busy = trace.busy_ns(ops, w)
        if coll is not None and busy > 0:
            shares.append(100.0 * coll / busy)
    return max(shares) if shares else None


def _calls(run) -> list:
    calls = []
    for d, p in run.steps:
        if d:
            calls.append(("decode", d))
        if p:
            calls.append(("prefill", p))
    return calls


def mfu(run) -> float | None:
    """Per cent of the cell's chips' peak: the GEMM operations that the
    tokens processed in the traced window need (every layer for each
    decoded or prefilled token, the head for each generated token) over
    the window times the chips times one chip's peak.  Attention's score
    and value products are not counted, so this is a floor."""
    dec = sum(d for d, _ in run.steps)
    pre = sum(p for _, p in run.steps)
    if not dec + pre:
        return None
    flops = ((dec + pre) * work.stack_flops_per_token(run.cfg)
             + (dec + run.first_tokens) * work.head_flops_per_token(run.cfg))
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["peak_flops"])


def gemm_roofline(run) -> float | None:
    """Per cent of the roofline reached by the protected GEMM sites on
    one device: the least time of the sites' calls in the traced window
    (each call the larger of its operations over the peak and its bytes
    over the bandwidth, from the configuration's widths cut to one
    device's shard), over that device's time in every op under the
    sites' ``abft[..][site]`` scopes, collectives aside."""
    spent = trace.site_seconds(run.ops)
    least = work.window_work(run.cfg, _calls(run), run.peaks["peak_flops"],
                             run.peaks["hbm_bytes_per_s"], run.chips)
    sites = [s for s in least if spent.get(s)]
    if not sites or not _calls(run):
        return None
    return 100.0 * sum(least[s] for s in sites) / sum(spent[s]
                                                      for s in sites)


def abft_check_share(run) -> float | None:
    """Per cent of device busy time spent in the ``global`` scheme's
    checks: ops under an ``abft[global][site]`` scope other than the
    site's matrix product."""
    busy = trace.busy_ns(run.ops, run.window_s * 1e9) / 1e9
    checks = trace.check_seconds(run.ops)
    if checks is None or busy <= 0:
        return None
    return 100.0 * checks / busy


def queue_wait_p95_ms(run) -> float | None:
    """95th percentile of the wait from a request's due time to its
    admission into the engine, over the whole window."""
    return stats.percentile_ms(run.queue_waits, 95)


def compiles_in_window(run) -> float:
    """Compilations and compile-cache loads inside the measured window."""
    return float(run.compiles)
