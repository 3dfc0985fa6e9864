"""Benchmark harness: one module per paper table/figure (+ the beyond-
paper serving and kernel benches). Prints ``name,us_per_call,derived``
CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig12]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "fig3_write_policy",
    "fig10_cache_size",
    "fig12_latency",
    "fig14_endurance",
    "fig15_vm_scaling",
    "fig17_intervals",
    "serving_two_tier",
    "kernels_bench",
    "trace_streaming",
    "classification_bench",
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time()-t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
