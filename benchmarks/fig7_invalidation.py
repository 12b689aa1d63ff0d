"""Fig. 7: invalidation overhead — remote accesses, invalidations and
flushed pages as a fraction of total accesses, per workload x blades."""

from __future__ import annotations

import time

from benchmarks.common import (emit, engine_from_argv, save_json,
                               run_workload_with_engine)
from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    choice = engine_from_argv()
    rows = []
    for wl in ("TF", "GC", "M_A", "M_C"):
        for nb in (2, 4, 8):
            t0 = time.perf_counter()
            r = run_workload_with_engine(
                choice, "mind", wl, num_compute_blades=nb,
                             threads_per_blade=4, accesses_per_thread=600)
            wall = (time.perf_counter() - t0) * 1e6
            n = max(1, r.stats.accesses)
            row = {
                "workload": wl, "blades": nb,
                "remote_frac": r.stats.remote_fetches / n,
                "inval_frac": r.stats.invalidations / n,
                "flushed_frac": r.stats.flushed_pages / n,
                "false_inv_frac": r.stats.false_invalidated_pages / n,
                "engine_used": r.engine,
            }
            rows.append(row)
            emit(f"fig7/{wl}/b{nb}", wall,
                 f"remote={row['remote_frac']:.3f};"
                 f"inval={row['inval_frac']:.3f};"
                 f"flush={row['flushed_frac']:.3f}")
    save_json("fig7_invalidation", rows)


if __name__ == "__main__":
    main()
