#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, for one cell, in
one process: for each seed, the cell's own window and load, then the
widest logit gap of the served tokens (the program) and of the tokens
that the fp8 reference puts first at the same positions (the control).

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
      --seconds 30 [--control-seeds 3] [--out bench_out]

The engine is built and warmed once; each seed brings its own weights
(swapped into the engine's executor) and its own traffic.  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import correct, harness, program, spec, traffic, weights

    cell = spec.cell(args.workload)
    cfg, settings = cell.config, cell.settings
    seeds = [int(s) for s in args.seeds.split(",")]
    drv, w_dev, devs, _ = harness.setup(cell, seeds[0])
    mesh = harness.cell_mesh(devs)
    ref = spec.reference(cfg["architecture"])
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            drv.engine.executor.params = None
            w_dev = None
            gc.collect()
            w_dev = weights.make(cfg, seed, mesh)
            drv.engine.executor.params = program.params(drv.engine.model,
                                                        cfg, w_dev)
        items = traffic.stream(cell.mix, seed, cfg["vocab_size"],
                               rate=settings.get("rate_per_s"))
        w = drv.window(items, args.seconds, loop=cell.mix["loop"],
                       clients=settings.get("clients", 0))
        finished = [r for r in w.requests if r.done and not r.error
                    and r.generated]
        drv.drain()
        chosen = correct.sample(finished, settings["check_tokens"], seed)
        t = time.perf_counter()
        g = correct.gaps(ref, w_dev, cfg, chosen)
        row = {"seed": seed, "requests": len(chosen), "tokens": int(g.size),
               "program_max_gap": float(g.max()),
               "program_p99_gap": float(np.percentile(g, 99)),
               "program_nonzero": int((g > 0).sum()),
               "detections": w.counters.get("faults_detected", 0),
               "failed": sum(1 for r in w.requests if r.error),
               "longest_served": max(len(r.generated) for r in chosen)}
        if i < args.control_seeds:
            c = correct.gaps(ref, w_dev, cfg, chosen, precision="fp8")
            row.update(control_max_gap=float(c.max()),
                       control_p50_gap=float(np.median(c)),
                       control_nonzero=int((c > 0).sum()))
        row["reference_s"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["program_max_gap"] for r in rows)
    ctrl = [r["control_max_gap"] for r in rows if "control_max_gap" in r]
    summary = {"workload": cell.name, "lower": lower,
               "upper": min(ctrl) if ctrl else None, "rows": rows,
               "device": jax.devices()[0].device_kind}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"calibrate_{cell.name}.json"),
                  "w") as fh:
            json.dump(summary, fh, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
