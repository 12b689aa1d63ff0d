"""Fig. 9: switch resource bottlenecks — directory residency over time,
match-action entries vs dataset size (MIND vs page-based), allocation
load-balance fairness."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache
from repro.core.address_space import GlobalAddressSpace
from repro.core.allocator import MemoryAllocator
from repro.core.emulator import run_workload
from repro.core.protection import ProtectionTable
from repro.core.types import PAGE_SIZE, Perm


def directory_timeline():
    """Fig. 9 (left): directory entries over time per workload."""
    rows = []
    for wl in ("TF", "GC", "M_A", "M_C"):
        t0 = time.perf_counter()
        r = run_workload("mind", wl, num_compute_blades=4,
                         threads_per_blade=4, accesses_per_thread=800,
                         epoch_us=2_000.0)
        wall = (time.perf_counter() - t0) * 1e6
        tl = r.directory_timeline or [0]
        rows.append({"workload": wl, "timeline": tl, "peak": max(tl)})
        emit(f"fig9_left/{wl}", wall, f"peak_entries={max(tl)}")
    return rows


def match_action_entries():
    """Fig. 9 (center): translation+protection entries vs heap size —
    MIND's per-blade range partition vs per-page tables."""
    rows = []
    for heap_gb in (1, 4, 16, 64):
        gas = GlobalAddressSpace()
        for _ in range(8):
            gas.add_blade()
        alloc = MemoryAllocator(gas)
        prot = ProtectionTable()
        # Realistic allocation mix: a few big vmas per process (glibc
        # arenas are large + pow2, §4.2).
        remaining = heap_gb << 30
        pdid = 1
        while remaining > 0:
            size = min(remaining, 256 << 20)
            vma = alloc.mmap(pdid, size)
            prot.grant_vma(vma)
            remaining -= size
            pdid = pdid % 16 + 1
        mind_entries = gas.num_translation_entries() + prot.num_entries()
        pages_4k = (heap_gb << 30) // PAGE_SIZE
        pages_2m = (heap_gb << 30) // (2 << 20)
        pages_1g = (heap_gb << 30) // (1 << 30)
        rows.append({"heap_gb": heap_gb, "mind": mind_entries,
                     "pt_4k": pages_4k, "pt_2m": pages_2m, "pt_1g": pages_1g})
        emit(f"fig9_center/heap{heap_gb}G", 0.0,
             f"mind={mind_entries};4k={pages_4k};2m={pages_2m};1g={pages_1g}")
    return rows


def load_balance_mixes() -> dict:
    """The Fig. 9 (right) allocation-size mixes, seeded — shared with
    ``benchmarks/alloc_bench.py`` so the fit-policy comparison runs the
    same fig9-style static cells."""
    rng = np.random.default_rng(0)
    return {
        "TF-like": rng.choice([64 << 20, 256 << 20], 64),
        "M-like": rng.choice([1 << 20, 4 << 20, 16 << 20], 400),
    }


def load_balance():
    """Fig. 9 (right): Jain's fairness of per-blade allocation."""
    rows = []
    for dist, sizes in load_balance_mixes().items():
        gas = GlobalAddressSpace()
        for _ in range(8):
            gas.add_blade()
        alloc = MemoryAllocator(gas)
        for i, s in enumerate(sizes):
            alloc.mmap(i % 8 + 1, int(s))
        jain = alloc.jain_fairness()
        # 1 GB "huge page" strawman: whole allocations land on one blade.
        per_blade = np.zeros(8)
        for i, s in enumerate(sizes):
            per_blade[i % 3] += (int(s) + (1 << 30) - 1) // (1 << 30)
        jain_1g = float(per_blade.sum() ** 2 / (8 * (per_blade ** 2).sum()))
        rows.append({"dist": dist, "jain_mind": jain, "jain_1g": jain_1g})
        emit(f"fig9_right/{dist}", 0.0,
             f"jain_mind={jain:.3f};jain_1g={jain_1g:.3f}")
    return rows


def main() -> None:
    enable_compile_cache()
    out = {
        "left": directory_timeline(),
        "center": match_action_entries(),
        "right": load_balance(),
    }
    save_json("fig9_resources", out)


if __name__ == "__main__":
    main()
