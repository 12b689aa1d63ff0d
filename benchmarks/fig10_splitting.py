"""Fig. 10: Bounded Splitting — storage/performance trade-off vs fixed
region sizes (left); epoch & initial-region-size sensitivity (right)."""

from __future__ import annotations

import time

from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache
from repro.core.emulator import run_workload


def fixed_vs_adaptive():
    """Fixed granularities (16 KB / 256 KB / 2 MB, splitting disabled) vs
    bounded splitting: directory entries vs false invalidations."""
    rows = []
    for wl in ("TF", "GC"):
        for label, log2, split in [
            ("fixed16K", 14, False), ("fixed256K", 18, False),
            ("fixed2M", 21, False), ("bounded", 14, True),
        ]:
            t0 = time.perf_counter()
            r = run_workload(
                "mind", wl, num_compute_blades=4, threads_per_blade=4,
                accesses_per_thread=600, initial_region_log2=log2,
                max_region_log2=21, splitting_enabled=split,
                epoch_us=2_000.0)
            wall = (time.perf_counter() - t0) * 1e6
            entries = (max(r.directory_timeline)
                       if r.directory_timeline else 0)
            rows.append({
                "workload": wl, "config": label,
                "false_inv": r.stats.false_invalidated_pages,
                "dir_entries": entries,
            })
            emit(f"fig10_left/{wl}/{label}", wall,
                 f"false_inv={r.stats.false_invalidated_pages};"
                 f"entries={entries}")
    return rows


def sensitivity():
    """Epoch length and initial region size sweeps (normalized as in the
    paper: by the value at 2 MB initial / largest epoch)."""
    rows = []
    for wl in ("TF", "GC"):
        # epoch sweep
        base = None
        for epoch_us in (500.0, 2_000.0, 10_000.0):
            r = run_workload("mind", wl, num_compute_blades=4,
                             threads_per_blade=4, accesses_per_thread=600,
                             epoch_us=epoch_us)
            fi = r.stats.false_invalidated_pages
            base = base or max(1, fi)
            rows.append({"workload": wl, "epoch_us": epoch_us,
                         "false_inv_norm": fi / base})
            emit(f"fig10_epoch/{wl}/e{int(epoch_us)}", 0.0,
                 f"false_inv_norm={fi/base:.3f}")
        # initial region size sweep
        base = None
        for log2 in (21, 18, 14):
            r = run_workload("mind", wl, num_compute_blades=4,
                             threads_per_blade=4, accesses_per_thread=600,
                             initial_region_log2=log2, epoch_us=2_000.0)
            fi = r.stats.false_invalidated_pages
            base = base or max(1, fi)
            rows.append({"workload": wl, "init_log2": log2,
                         "false_inv_norm": fi / base})
            emit(f"fig10_init/{wl}/r{1 << log2}", 0.0,
                 f"false_inv_norm={fi/base:.3f}")
    return rows


def main() -> None:
    enable_compile_cache()
    out = {"left": fixed_vs_adaptive(), "right": sensitivity()}
    save_json("fig10_splitting", out)


if __name__ == "__main__":
    main()
