"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig8]

Emits ``name,us_per_call,derived`` CSV to stdout; JSON artifacts land in
benchmarks/results/.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = [
    "fig6_scaling",  # Fig. 6  intra/inter-blade scaling
    "fig7_invalidation",  # Fig. 7  invalidation overhead
    "fig8_latency",  # Fig. 8  transition latency / throughput / breakdown
    "fig9_resources",  # Fig. 9  switch resources + fairness
    "fig10_splitting",  # Fig. 10 bounded splitting
    "dataplane_bench",  # batched data-plane engine vs scalar emulator
    "kernel_bench",  # Pallas kernels microbench
    "serving_bench",  # MIND paged-KV serving integration
    "roofline",  # §Roofline collation from the dry-run
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--engine", choices=("scalar", "batched"),
                    default="scalar",
                    help="data-plane engine for fig6/7/8 (modules re-read "
                         "it from argv via benchmarks.common)")
    args = ap.parse_args()
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
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {mod_name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
