"""Benchmark harness entrypoint: one bench per paper table/figure plus the
dry-run roofline table.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only substring]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_families, bench_fig34_speedup,
                            bench_kv_quant, bench_prefix_cache,
                            bench_proposers, bench_sampling, bench_serving,
                            bench_table2_heads, roofline)
    suites = [
        ("table2", bench_table2_heads.run),
        ("fig3+fig4+eq2", bench_fig34_speedup.run),
        ("serving", bench_serving.run),
        ("kv_quant", bench_kv_quant.run),
        ("sampling", bench_sampling.run),
        ("prefix_cache", bench_prefix_cache.run),
        ("proposers", bench_proposers.run),
        ("families", bench_families.run),
        ("roofline", roofline.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
