"""Benchmark driver — one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table2,fig7]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ["table2", "fig6", "fig7", "fig8", "scaleout", "halo", "gather",
          "serve", "faults", "dynamic", "table3", "table4", "kernels",
          "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced datasets/configs (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SUITES)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (table2_training, fig6_scalability, fig7_sampling,
                            fig8_parallelism, fig_scaleout, fig_halo,
                            fig_gather, fig_serve, fig_faults, fig_dynamic,
                            table3_surrogate, table4_autotune, kernels_bench,
                            roofline)
    mods = {"table2": table2_training, "fig6": fig6_scalability,
            "fig7": fig7_sampling, "fig8": fig8_parallelism,
            "scaleout": fig_scaleout, "halo": fig_halo,
            "gather": fig_gather, "serve": fig_serve,
            "faults": fig_faults, "dynamic": fig_dynamic,
            "table3": table3_surrogate, "table4": table4_autotune,
            "kernels": kernels_bench, "roofline": roofline}

    print("name,us_per_call,derived")
    failures = []
    for name in SUITES:
        if name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            mods[name].run(quick=args.quick)
        except Exception:  # noqa: BLE001 — run every suite
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
