#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line of
standard output.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace.  Every run
checks the served tokens against the plain reference (``correct``).  It
needs a TPU: without one it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for a sample of the trace (with "
                         "--trace 1)")
    args = ap.parse_args(argv)

    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START, out_dir=args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
