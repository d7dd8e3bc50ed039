# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: one module per paper table/figure.

  fig4   aggregate arithmetic intensity per network      (paper Fig. 4)
  fig5   per-layer AI heterogeneity + per-site selection (paper Fig. 5)
  fig8   per-network ABFT overhead, 3 schemes            (paper Figs. 8-11)
  fig12  square-GEMM scheme sweep + crossovers           (paper Fig. 12)
  table1 per-scheme redundant-op accounting              (paper Table 1)
  roofline  dry-run roofline terms per cell              (EXPERIMENTS §Roofline)
"""

import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig4_aggregate_intensity,
        fig5_layer_intensity,
        fig8_11_overhead,
        fig12_square_sweep,
        roofline_summary,
        table1_op_counts,
    )

    modules = {
        "fig4": fig4_aggregate_intensity,
        "fig5": fig5_layer_intensity,
        "fig8": fig8_11_overhead,
        "fig12": fig12_square_sweep,
        "table1": table1_op_counts,
        "roofline": roofline_summary,
    }
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        if only and name != only:
            continue
        for r in mod.run():
            print(r)


if __name__ == "__main__":
    main()
