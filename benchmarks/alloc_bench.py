"""Allocation-strategy bench (ISSUE 10): fit policies under churn.

A fig9-style cell per **fit policy x churn profile**: each seeded
alloc/free-heavy churn trace (``repro.core.traces.alloc_churn_trace``)
is replayed twice per policy —

* a **bare pass** against a raw :class:`MemoryAllocator` timing pure
  allocator decisions (``alloc_wall_us``, ``kevents_per_s``), and
* a **resource pass** through a full :class:`ControlPlane`
  (``sys_mmap``/``sys_munmap`` with §4.4 mmap-time pre-population and
  directory teardown on unmap) sampling the switch-resource trajectory
  every event: protection-table TCAM entries (peak/final), directory
  regions (peak/final), live vmas.

Reported per cell: external fragmentation, peak/final TCAM-entry
count, peak/final directory-region count, Jain's fairness across
blades, allocator wall time, failed allocations, and reserved-vs-
requested bytes (internal fragmentation).  Fragmentation is the
coherence-throughput knob here: every live vma costs TCAM entries and
every allocated byte carries directory regions, so a sloppier fit
policy is also switch-SRAM pressure.

The Fig. 9 (right) static allocation mixes
(``benchmarks.fig9_resources.load_balance_mixes``) run as extra cells
per policy, so the paper's load-balance experiment extends across fit
policies.

Always-on assertions (the ``--perf-floor``-style contract):

* conservation — every blade's ``free + reserved == capacity`` after
  every cell, and draining the trace returns all requested bytes;
* §4.4 TCAM bound — pow2-rounded vmas cost one TCAM entry each, so
  sampled protection entries never exceed live vmas;
* per-policy ``ControlPlane.snapshot``/``restore`` round-trip — the
  restored allocator makes byte-identical follow-on placements;
* ``--perf-floor X`` additionally asserts every bare pass sustains
  >= X k-events/s (the CI smoke runs X=2).

Usage: PYTHONPATH=src python -m benchmarks.alloc_bench
       [--quick] [--perf-floor X] [--events N]

Results land in ``benchmarks/results/BENCH_alloc.json`` (field
reference: docs/BENCHMARKS.md).
"""

from __future__ import annotations

import argparse
import time

from benchmarks.common import emit, save_json
from benchmarks.fig9_resources import load_balance_mixes
from repro.compile_cache import enable_compile_cache
from repro.core.address_space import GlobalAddressSpace
from repro.core.allocator import MemoryAllocator
from repro.core.alloc_policies import POLICIES
from repro.core.control_plane import ControlPlane
from repro.core.switch import make_mmu
from repro.core.traces import MMAP, CHURN_PROFILES, alloc_churn_trace
from repro.core.types import Perm

POLICY_NAMES = tuple(POLICIES)  # ("first_fit", "buddy", "segregated")
MEM_BLADES = 8
COMPUTE_BLADES = 4
BLADE_CAPACITY = 2 << 30  # 2 GB/blade: enough pressure that fit matters
DIR_SLOTS = 4_096  # small switch SRAM so directory churn is visible


def _bare_allocator(policy: str) -> MemoryAllocator:
    gas = GlobalAddressSpace()
    for _ in range(MEM_BLADES):
        gas.add_blade(BLADE_CAPACITY)
    return MemoryAllocator(gas, policy=policy)


def _check_books(alloc: MemoryAllocator) -> None:
    for b in alloc.blades.values():
        b.check_conservation()


def replay_bare(policy: str, trace) -> dict:
    """Pure allocator churn: policy decision cost + fragmentation."""
    alloc = _bare_allocator(policy)
    base_of: dict[int, int | None] = {}
    failures = 0
    requested = 0
    t0 = time.perf_counter()
    for i, kind, pdid, arg in trace.events():
        if kind == MMAP:
            try:
                base_of[i] = alloc.mmap(pdid, arg).base
                requested += arg
            except MemoryError:
                base_of[i] = None
                failures += 1
        else:
            base = base_of.pop(arg)
            if base is not None:
                alloc.munmap(base)
    wall_us = (time.perf_counter() - t0) * 1e6
    _check_books(alloc)
    live_bytes = sum(v.length for v in alloc.vmas.values())
    reserved = sum(b.policy.reserved_bytes for b in alloc.blades.values())
    row = {
        "alloc_wall_us": round(wall_us, 1),
        "kevents_per_s": round(len(trace) / wall_us * 1e3, 2),
        "alloc_failures": failures,
        "external_fragmentation": round(alloc.external_fragmentation(), 4),
        "jain_fairness": round(alloc.jain_fairness(), 4),
        "live_vmas": len(alloc.vmas),
        "live_bytes": live_bytes,
        "reserved_bytes": reserved,
        "internal_overhead": round(reserved / live_bytes - 1.0, 4) if live_bytes else 0.0,
    }
    # Drain: every surviving allocation must free cleanly (validated
    # frees — a policy that corrupted its books raises here).
    for base in [b for b in base_of.values() if b is not None]:
        alloc.munmap(base)
    _check_books(alloc)
    assert sum(alloc.allocation_by_blade().values()) == 0
    return row


def replay_resources(policy: str, trace) -> dict:
    """Control-plane churn: switch-resource (TCAM + directory) trajectory."""
    mmu, alloc = make_mmu(
        num_memory_blades=MEM_BLADES, num_compute_blades=COMPUTE_BLADES,
        cache_bytes_per_blade=1 << 20, max_directory_entries=DIR_SLOTS,
        alloc_policy=policy, blade_capacity=BLADE_CAPACITY)
    cp = ControlPlane(mmu, alloc)
    base_of: dict[int, tuple[int, int] | None] = {}
    peak_tcam = peak_dir = peak_live = 0
    for i, kind, pdid, arg in trace.events():
        if kind == MMAP:
            try:
                vma = cp.sys_mmap(pdid, arg, Perm.RW,
                                  requesting_blade=pdid % COMPUTE_BLADES).vma
                base_of[i] = (pdid, vma.base)
            except MemoryError:
                base_of[i] = None
        else:
            tgt = base_of.pop(arg)
            if tgt is not None:
                assert cp.sys_munmap(*tgt).retval == 0
        tcam = mmu.protection.num_entries()
        live = len(alloc.vmas)
        assert tcam <= live, (
            f"§4.4 violated: {tcam} TCAM entries for {live} pow2 vmas")
        peak_tcam = max(peak_tcam, tcam)
        peak_dir = max(peak_dir, mmu.engine.directory.num_entries())
        peak_live = max(peak_live, live)
    _check_books(alloc)
    row = {
        "peak_tcam_entries": peak_tcam,
        "final_tcam_entries": mmu.protection.num_entries(),
        "peak_directory_regions": peak_dir,
        "final_directory_regions": mmu.engine.directory.num_entries(),
        "peak_live_vmas": peak_live,
        "final_live_vmas": len(alloc.vmas),
    }
    # Failover: snapshot -> restore must re-carve exact ranges and make
    # the same follow-on placement decision (ISSUE 10 tentpole contract).
    snap = cp.snapshot()
    cp2 = ControlPlane.restore(snap, cache_bytes_per_blade=1 << 20,
                               num_compute_blades=COMPUTE_BLADES)
    assert cp2.allocator.allocation_by_blade() == alloc.allocation_by_blade()
    assert cp2.allocator.free_bytes_by_blade() == alloc.free_bytes_by_blade()
    v1 = cp.sys_mmap(1, 123_456).vma
    v2 = cp2.sys_mmap(1, 123_456).vma
    assert (v1.base, v1.blade_id) == (v2.base, v2.blade_id), \
        f"{policy}: restored allocator diverged on the next placement"
    return row


def fig9_cells() -> list[dict]:
    """Fig. 9 (right) static mixes, extended across fit policies."""
    rows = []
    for dist, sizes in load_balance_mixes().items():
        for policy in POLICY_NAMES:
            gas = GlobalAddressSpace()
            for _ in range(MEM_BLADES):
                gas.add_blade()
            alloc = MemoryAllocator(gas, policy=policy)
            for i, s in enumerate(sizes):
                alloc.mmap(i % MEM_BLADES + 1, int(s))
            _check_books(alloc)
            rows.append({
                "dist": dist, "policy": policy,
                "jain_fairness": round(alloc.jain_fairness(), 4),
                "external_fragmentation": round(alloc.external_fragmentation(), 4),
            })
            emit(f"alloc_fig9/{dist}/{policy}", 0.0,
                 f"jain={rows[-1]['jain_fairness']:.3f}")
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer events per cell (CI smoke)")
    ap.add_argument("--events", type=int, default=None)
    ap.add_argument("--perf-floor", type=float, default=None, metavar="X",
                    help="assert every bare pass sustains >= X k-events/s")
    args = ap.parse_args()
    num_events = args.events or (600 if args.quick else 4_000)

    cells = []
    for profile in CHURN_PROFILES:
        trace = alloc_churn_trace(profile=profile, num_events=num_events)
        n_mmap = int((trace.kinds == MMAP).sum())
        for policy in POLICY_NAMES:
            row = {"policy": policy, "profile": profile,
                   "events": len(trace), "mmaps": n_mmap}
            row.update(replay_bare(policy, trace))
            row.update(replay_resources(policy, trace))
            cells.append(row)
            emit(f"alloc_churn/{profile}/{policy}", row["alloc_wall_us"],
                 f"kevents_s={row['kevents_per_s']};"
                 f"frag={row['external_fragmentation']:.3f};"
                 f"peak_tcam={row['peak_tcam_entries']};"
                 f"peak_dir={row['peak_directory_regions']};"
                 f"jain={row['jain_fairness']:.3f}")
            if args.perf_floor is not None:
                assert row["kevents_per_s"] >= args.perf_floor, (
                    f"{policy}/{profile}: {row['kevents_per_s']} kevents/s "
                    f"below the {args.perf_floor} floor")

    out = {
        "meta": {
            "num_events": num_events,
            "mem_blades": MEM_BLADES,
            "blade_capacity": BLADE_CAPACITY,
            "directory_slots": DIR_SLOTS,
            "policies": list(POLICY_NAMES),
            "profiles": list(CHURN_PROFILES),
            "quick": bool(args.quick),
        },
        "cells": cells,
        "fig9_load_balance": fig9_cells(),
    }
    save_json("BENCH_alloc", out)
    print(f"# wrote benchmarks/results/BENCH_alloc.json "
          f"({len(cells)} churn cells)")


if __name__ == "__main__":
    main()
