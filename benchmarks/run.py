"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig9,fig10,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    chaos_bench,
    decode_bench,
    fig9_activation_sweep,
    fig10_vs_bramac,
    fig11_parallelism_ablation,
    fig12_vs_dsp,
    kernel_bench,
    prefix_bench,
    quant_error,
    roofline_table,
    serving_bench,
    spec_bench,
    swap_bench,
    table3_intralayer,
    tier_bench,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "fig9": fig9_activation_sweep,
    "fig10": fig10_vs_bramac,
    "fig11": fig11_parallelism_ablation,
    "fig12": fig12_vs_dsp,
    "table3": table3_intralayer,
    "quant_error": quant_error,
    "kernels": kernel_bench,
    "decode": decode_bench,
    "roofline": roofline_table,
    "serving": serving_bench,
    "prefix": prefix_bench,
    "spec": spec_bench,
    "swap": swap_bench,
    "tiers": tier_bench,
    "chaos": chaos_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            MODULES[name].run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
