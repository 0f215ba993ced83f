"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Heavy suites honour
``--fast`` (used by tests) to shrink step counts.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only table1,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (ensemble_bench, fig23_curves, kernel_bench,
                            plan_bench, roofline_report, serve_bench, table1,
                            xnor_bench, xnor_conv_bench)
    suites = {
        "table1": table1.main,
        "fig23": fig23_curves.main,
        "kernels": kernel_bench.main,
        "roofline": roofline_report.main,
        "xnor": xnor_bench.main,
        "xnor_conv": xnor_conv_bench.main,
        "plans": plan_bench.main,
        "serve": serve_bench.main,
        "ensemble": ensemble_bench.main,
    }
    selected = (args.only.split(",") if args.only else list(suites))
    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        try:
            for line in suites[name](fast=args.fast):
                print(line)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
