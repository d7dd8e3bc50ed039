#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: serve the cell's traffic at each
rate for ``--seconds`` and report, per rate, the share of requests due in
the window that meet both limits (time to first token and mean gap
between tokens each at most 5x its median at the lowest rate), and
whether the front-end queue grew.  The knee is the highest rate at which
90% meet both with no growing queue; the cell's rate is 0.8x the knee.

  python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 \
      --seconds 30 [--seed 1] [--out bench_out]

One engine, built and warmed once, serves every rate.  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness, spec, stats, traffic

    cell = spec.cell(args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("a knee sweep needs an open-loop cell")
    drv, _, _, _ = harness.setup(cell, args.seed)
    rows, base = [], None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        items = traffic.stream(cell.mix, args.seed, cell.config["vocab_size"],
                               rate=rate)
        w = drv.window(items, args.seconds, loop="open")
        mid_q = None
        due = [r for r in w.requests if r.due < w.t1]
        ttft = [r.times[0] - r.due for r in due if r.times]
        itl = [float(np.mean(np.diff(r.times))) for r in due
               if len(r.times) > 1]
        # backlog: requests due but not yet admitted, at the middle and at
        # the end of the window
        mid = w.t0 + (w.t1 - w.t0) / 2
        mid_q = sum(1 for r in due if r.due <= mid and
                    (r.admitted_at is None or r.admitted_at > mid))
        end_q = sum(1 for r in due if r.admitted_at is None
                    or r.admitted_at > w.t1)
        if base is None:
            base = (float(np.median(ttft)), float(np.median(itl)))
        ok = sum(1 for r in due if r.times and r.times[0] - r.due
                 <= 5 * base[0] and (len(r.times) < 2 or float(
                     np.mean(np.diff(r.times))) <= 5 * base[1]))
        row = {"rate": rate, "due": len(due),
               "attainment": ok / max(len(due), 1),
               "ttft_p50_ms": stats.percentile_ms(ttft, 50),
               "ttft_p95_ms": stats.percentile_ms(ttft, 95),
               "itl_mean_p50_ms": float(np.median(itl)) * 1e3,
               "backlog_mid": mid_q, "backlog_end": end_q,
               "tokens_per_s": harness.end_to_end(w, "open")[0][
                   "tokens_per_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        drv.drain()
    knee = max((r["rate"] for r in rows if r["attainment"] >= 0.9
                and r["backlog_end"] <= max(r["backlog_mid"], 2)),
               default=None)
    summary = {"workload": cell.name, "knee": knee,
               "rate_at_0.8_knee": 0.8 * knee if knee else None,
               "limits_ms": {"ttft": 5e3 * base[0], "itl_mean": 5e3 * base[1]},
               "rows": rows}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"sweep_{cell.name}.json"),
                  "w") as fh:
            json.dump(summary, fh, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
