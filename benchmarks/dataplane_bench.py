"""Batched data-plane engine vs the scalar emulator: replay throughput.

The ISSUE 1 acceptance benchmark: a 4-blade zipfian (YCSB-A) trace is
replayed through both engines; the batched pipeline must sustain >= 10x
the scalar emulator's accesses/second while producing identical
coherence statistics.  Results land in
``benchmarks/results/BENCH_dataplane.json`` so the perf trajectory is
tracked across PRs.

Bounded-Splitting epochs run Python control-plane work that both
engines share; the headline number therefore disables splitting (pure
data-plane replay) and a second configuration reports the paper-style
100 ms-epoch setting.

The ISSUE 2 acceptance benchmark rides along: a fig7-style TF
capacity-pressure cell (initial regions > directory SRAM slots, the
"TF at 8 blades" case from the ROADMAP) is replayed through the seed's
O(n)-scan eviction path, the O(1) LRU scalar path and the batched
engine with on-device eviction packets; the before/after eviction
throughput lands in ``benchmarks/results/BENCH_eviction.json`` and the
LRU paths must beat the seed scan by >= 5x.

The ISSUE 3 acceptance benchmark: a *blade-cache* pressure cell
(per-blade working set ~2-4x the blade page cache, mixed reads and
writes so both dirty write-backs and clean drops fire) — the fig6/fig7
memory-pressure regime the batched engine used to refuse outright.
Replayed scalar vs batched (cache-occupancy pre-pass + eviction
packets); results land in
``benchmarks/results/BENCH_cache_eviction.json`` and batched must beat
scalar by >= 5x with identical stats.

ISSUE 4 targets ride on the same cells: the paper-style
``zipfian_100ms_epochs`` configuration must reach >= 8x scalar
(speculative epoch chunking) and the cache/directory pressure cells
>= 25x (vectorized pre-pass fast paths).  Every row now carries a
``phases`` dict — wall seconds per engine phase (host pre-passes,
scheduling, device replay, latency reconstruction, epoch control,
speculation overhead) — so future perf PRs have a phase-level
trajectory instead of a single wall number.

The ISSUE 5 acceptance benchmark: a multi-switch *sharded-directory*
scaling cell — the same deterministic cross-shard conflict trace
(`repro.core.traces.sharded_conflict_trace`) replayed on 1/2/4-shard
``ShardedRack``s, scalar vs batched (one TCAM/MSI kernel invocation
per shard).  Coherence stats must be byte-identical to the
single-switch oracle in every cell, and the emulated runtime must
exceed the oracle's by exactly the cross-shard hop total.  Results
land in ``benchmarks/results/BENCH_sharded.json``.

The ISSUE 7 acceptance benchmark: a skewed 2-shard cell with per-shard
SRAM budgets where the online rebalancer migrates the hot VA blocks at
the first epoch boundary — pre/post shard-access split and occupancy,
migration counts and charged microseconds, and the batched-vs-scalar
speedup with the rebalancer live land in
``benchmarks/results/BENCH_rebalance.json``.

The ISSUE 8 acceptance benchmark: the GAM and FastSwap baseline cells —
fig6 sweeps used to single-step these through the scalar emulator —
replayed through the vectorized baseline engines
(:mod:`repro.dataplane.baselines`), asserting identical stats / modeled
runtime / latency breakdown and a >= 5x speedup per cell; results land
in ``benchmarks/results/BENCH_baselines.json``.

Usage: PYTHONPATH=src python -m benchmarks.dataplane_bench
       [--quick] [--perf-floor X]

``--perf-floor X`` turns the speedup targets into hard assertions at a
conservative floor X (the CI perf-smoke step runs with ``X=2``).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache
from repro.core import traces as T
from repro.core.directory import CacheDirectory
from repro.core.emulator import DisaggregatedRack
from repro.core.types import SwitchResources
from repro.dataplane.engine import PHASES

BLADES = 4
THREADS_PER_BLADE = 10

STAT_FIELDS = (
    "accesses", "local_hits", "remote_fetches", "invalidations",
    "invalidated_pages", "false_invalidated_pages", "flushed_pages",
)


def _rack(engine: str, **kw) -> DisaggregatedRack:
    return DisaggregatedRack(
        system="mind", num_compute_blades=BLADES,
        threads_per_blade=THREADS_PER_BLADE, engine=engine, **kw)


def _phases(result) -> dict:
    """Per-phase wall seconds of a batched run, keyed off the engine's
    frozen ``PHASES`` schema (see docs/BENCHMARKS.md 'phases' field
    reference) — a renamed or dropped phase fails here, not in a
    dashboard downstream."""
    assert set(result.phase_times) == set(PHASES), \
        f"phase_times drifted from PHASES: {sorted(result.phase_times)}"
    return {k: round(result.phase_times[k], 5) for k in PHASES}


def bench_config(trace, label: str, repeats: int, expect_identical: bool = True,
                 **rack_kw) -> dict:
    # Warm the batched path once with a full replay: jit compilation is
    # a per-process cost keyed on batch shapes, not a per-replay one.
    _rack("batched", **rack_kw).run(trace)

    def best_wall(engine: str):
        best, result = float("inf"), None
        for _ in range(repeats):
            rack = _rack(engine, **rack_kw)
            t0 = time.perf_counter()
            result = rack.run(trace)
            best = min(best, time.perf_counter() - t0)
        return best, result

    wall_b, rb = best_wall("batched")
    wall_s, rs = best_wall("scalar")
    n = len(trace)
    parity = {
        f: (getattr(rs.stats, f), getattr(rb.stats, f)) for f in STAT_FIELDS
    }
    identical = all(a == b for a, b in parity.values())
    max_drift = max(abs(a - b) / max(1, a) for a, b in parity.values())
    if identical:
        parity_note = "identical"
    elif expect_identical:
        parity_note = "DIVERGED"
    else:
        # Epoch timing is batch-granular in the batched engine; small
        # drift in the split/merge trajectory is expected here.
        parity_note = f"drift<={max_drift:.1%}"
    row = {
        "config": label,
        "accesses": n,
        "scalar_acc_per_s": n / wall_s,
        "batched_acc_per_s": n / wall_b,
        "speedup": wall_s / wall_b,
        "stats_identical": identical,
        "max_stat_drift": max_drift,
        "stats": {f: {"scalar": a, "batched": b}
                  for f, (a, b) in parity.items()},
        "runtime_us": {"scalar": rs.runtime_us, "batched": rb.runtime_us},
        "phases": _phases(rb),
    }
    emit(f"dataplane/{label}/scalar", wall_s / n * 1e6,
         f"acc_per_s={n / wall_s:.0f}")
    emit(f"dataplane/{label}/batched", wall_b / n * 1e6,
         f"acc_per_s={n / wall_b:.0f};speedup={wall_s / wall_b:.1f}x;"
         f"parity={parity_note}")
    return row


# --------------------------------------------------------------------- #
# ISSUE 2: directory capacity-eviction throughput (BENCH_eviction.json).
# --------------------------------------------------------------------- #
def bench_install_microbench(n_install: int, slots: int) -> dict:
    """Raw install throughput under capacity pressure: the seed O(n)
    scan vs the O(1) LRU recency lists, same victim sequence."""
    out = {"installs": n_install, "directory_slots": slots}
    for mode in ("scan", "lru"):
        d = CacheDirectory(
            resources=SwitchResources(max_directory_entries=slots),
            eviction=mode)
        lg = d.initial_region_log2
        t0 = time.perf_counter()
        for i in range(n_install):
            d.get_or_create((1 << 40) + i * (1 << lg))
        wall = time.perf_counter() - t0
        out[f"{mode}_wall_s"] = wall
        out[f"{mode}_installs_per_s"] = n_install / wall
        emit(f"eviction/install/{mode}", wall / n_install * 1e6,
             f"evictions={d.capacity_evictions}")
    out["speedup"] = out["scan_wall_s"] / out["lru_wall_s"]
    return out


def bench_tf_capacity_cell(quick: bool) -> dict:
    """fig7-style TF capacity cell, scaled so the seed scan path
    finishes: 8 blades x 4 threads streaming private tensors + a shared
    parameter area, with more initial regions than directory slots
    (ROADMAP's 'TF at 8 blades' case, ~49k regions vs 30k slots at full
    scale)."""
    threads = 32
    per_thread = 100 if quick else 300
    private_mb = 1 if quick else 3
    slots = 1500 if quick else 4000
    trace = T.tf_trace(num_threads=threads, accesses_per_thread=per_thread,
                       private_mb_per_thread=private_mb, shared_mb=8)
    regions = trace.arena_bytes >> 14
    kw = dict(system="mind", num_compute_blades=8, threads_per_blade=4,
              max_directory_entries=slots)

    def cell(engine: str, eviction: str):
        rack = DisaggregatedRack(engine=engine, directory_eviction=eviction,
                                 **kw)
        t0 = time.perf_counter()
        r = rack.run(trace)
        return time.perf_counter() - t0, r

    # Warm the batched path once (jit compilation is per-process).
    cell("batched", "lru")
    wall_scan, r_scan = cell("scalar", "scan")  # the seed O(n^2) path
    wall_lru, r_lru = cell("scalar", "lru")
    wall_b, r_b = cell("batched", "lru")
    parity = all(
        getattr(r_lru.stats, f) == getattr(r_b.stats, f) for f in STAT_FIELDS)
    scan_parity = all(
        getattr(r_lru.stats, f) == getattr(r_scan.stats, f)
        for f in STAT_FIELDS)
    out = {
        "workload": "TF (fig7-style capacity cell)",
        "blades": 8, "threads_per_blade": 4,
        "accesses": len(trace),
        "initial_regions": int(regions),
        "directory_slots": slots,
        "seed_scan_wall_s": wall_scan,
        "lru_scalar_wall_s": wall_lru,
        "lru_batched_wall_s": wall_b,
        "speedup_scalar_vs_seed": wall_scan / wall_lru,
        "speedup_batched_vs_seed": wall_scan / wall_b,
        "speedup_batched_vs_scalar": wall_lru / wall_b,
        "stats_identical_lru_scalar_vs_batched": parity,
        "stats_identical_scan_vs_lru": scan_parity,
        "phases": _phases(r_b),
    }
    emit("eviction/tf_capacity/seed_scan", wall_scan / len(trace) * 1e6,
         f"acc_per_s={len(trace)/wall_scan:.0f}")
    emit("eviction/tf_capacity/lru_scalar", wall_lru / len(trace) * 1e6,
         f"speedup_vs_seed={out['speedup_scalar_vs_seed']:.1f}x")
    emit("eviction/tf_capacity/lru_batched", wall_b / len(trace) * 1e6,
         f"speedup_vs_seed={out['speedup_batched_vs_seed']:.1f}x;"
         f"parity={'identical' if parity else 'DIVERGED'}")
    return out


def bench_eviction(quick: bool, perf_floor: float = 0.0) -> dict:
    micro = bench_install_microbench(
        n_install=6000 if quick else 45_000,
        slots=4000 if quick else 30_000)
    cell = bench_tf_capacity_cell(quick)
    out = {"install_microbench": micro, "tf_capacity_cell": cell}
    path = save_json("BENCH_eviction", out)
    print(f"# wrote {path}")
    assert cell["stats_identical_lru_scalar_vs_batched"], \
        "capacity-cell coherence stats diverged!"
    if cell["speedup_batched_vs_seed"] < 25.0:
        print(f"# WARNING: capacity-cell speedup "
              f"{cell['speedup_batched_vs_seed']:.1f}x below 25x target")
    if perf_floor:
        assert cell["speedup_batched_vs_seed"] >= perf_floor, \
            f"directory-pressure cell below {perf_floor}x floor"
    return out


# --------------------------------------------------------------------- #
# ISSUE 3: blade-cache eviction throughput (BENCH_cache_eviction.json).
# --------------------------------------------------------------------- #
def bench_cache_eviction(quick: bool, perf_floor: float = 0.0,
                         repeats: int = 2) -> dict:
    """Blade page-cache pressure cell: per-blade working set ~2-4x the
    blade cache, 50/50 reads and writes.  The regime swap-based
    baselines (FastSwap) are defined by and that the batched engine
    refused before ISSUE 3 — every miss-triggered insert can evict an
    LRU page, every dirty victim is a write-back."""
    from repro.core.types import PAGE_SIZE

    threads = BLADES * THREADS_PER_BLADE
    per_thread = 600 if quick else 3000
    ws_pages = 12_000 if quick else 24_000
    trace = T.uniform_trace(
        num_threads=threads, read_ratio=0.5, sharing_ratio=0.2,
        accesses_per_thread=per_thread, working_set_pages=ws_pages, seed=42)
    # Size each cache to ~1/3 of a blade's share of the working set:
    # shared pages are reachable from every blade, private pages from
    # one, so the touched set per blade is ~(shared + private/BLADES).
    shared = int(ws_pages * 0.2)
    per_blade_ws = shared + (ws_pages - shared) // BLADES
    cache_pages = max(64, per_blade_ws // 3)
    kw = dict(cache_bytes_per_blade=cache_pages * PAGE_SIZE,
              splitting_enabled=False)

    _rack("batched", **kw).run(trace)  # jit warm-up (per-process cost)
    wall_b, rb = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        rb = _rack("batched", **kw).run(trace)
        wall_b = min(wall_b, time.perf_counter() - t0)
    wall_s, rs = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        rs = _rack("scalar", **kw).run(trace)
        wall_s = min(wall_s, time.perf_counter() - t0)

    fields = STAT_FIELDS + ("evicted_dirty", "evicted_clean")
    parity = all(getattr(rs.stats, f) == getattr(rb.stats, f)
                 for f in fields)
    n = len(trace)
    out = {
        "workload": "uniform 50/50 r/w (blade-cache pressure cell)",
        "blades": BLADES, "threads_per_blade": THREADS_PER_BLADE,
        "accesses": n,
        "working_set_pages": ws_pages,
        "per_blade_working_set_pages": per_blade_ws,
        "cache_pages_per_blade": cache_pages,
        "ws_to_cache_ratio": per_blade_ws / cache_pages,
        "evicted_dirty": rs.stats.evicted_dirty,
        "evicted_clean": rs.stats.evicted_clean,
        "scalar_wall_s": wall_s,
        "batched_wall_s": wall_b,
        "scalar_acc_per_s": n / wall_s,
        "batched_acc_per_s": n / wall_b,
        "speedup_batched_vs_scalar": wall_s / wall_b,
        "stats_identical": parity,
        "runtime_us": {"scalar": rs.runtime_us, "batched": rb.runtime_us},
        "phases": _phases(rb),
    }
    emit("cache_eviction/scalar", wall_s / n * 1e6,
         f"acc_per_s={n / wall_s:.0f}")
    emit("cache_eviction/batched", wall_b / n * 1e6,
         f"acc_per_s={n / wall_b:.0f};speedup={wall_s / wall_b:.1f}x;"
         f"parity={'identical' if parity else 'DIVERGED'}")
    path = save_json("BENCH_cache_eviction", out)
    print(f"# wrote {path}")
    assert parity, "cache-eviction cell coherence stats diverged!"
    assert rs.stats.evicted_dirty > 0 and rs.stats.evicted_clean > 0, \
        "cache-pressure cell did not actually evict"
    if out["speedup_batched_vs_scalar"] < 25.0:
        print(f"# WARNING: cache-eviction speedup "
              f"{out['speedup_batched_vs_scalar']:.1f}x below 25x target")
    if perf_floor:
        assert out["speedup_batched_vs_scalar"] >= perf_floor, \
            f"cache-pressure cell below {perf_floor}x floor"
    return out


# --------------------------------------------------------------------- #
# ISSUE 5: multi-switch sharded-directory scaling (BENCH_sharded.json).
# --------------------------------------------------------------------- #
def bench_sharded(quick: bool, perf_floor: float = 0.0,
                  repeats: int = 2) -> dict:
    """Sharded-rack scaling cell: the *same* deterministic cross-shard
    conflict trace replayed on 1/2/4-shard ``ShardedRack``s, scalar vs
    batched (one TCAM/MSI kernel invocation per shard).  Every cell's
    coherence stats must be byte-identical to the single-switch scalar
    oracle — the sharding-invariance contract of tests/test_sharded.py
    — while the emulated runtime grows by exactly the cross-shard hop
    total and the wall-clock speedup stays >= the floor."""
    from repro.core.emulator import ShardedRack
    from repro.core.types import NetworkConstants

    threads = BLADES * THREADS_PER_BLADE
    per_thread = 500 if quick else 2500
    trace = T.sharded_conflict_trace(
        num_threads=threads, accesses_per_thread=per_thread,
        num_shards=4, blocks_per_shard=2, conflict_frac=0.5,
        write_frac=0.3, seed=42)
    kw = dict(system="mind", num_compute_blades=BLADES,
              threads_per_blade=THREADS_PER_BLADE, splitting_enabled=False)
    n = len(trace)
    oracle = DisaggregatedRack(engine="scalar", **kw).run(trace)
    hop = NetworkConstants().switch_to_switch_us
    cells = []
    for nsh in (1, 2, 4):
        # Warm the per-shard kernel shapes once (jit is per-process).
        ShardedRack(num_shards=nsh, engine="batched", **kw).run(trace)

        def best_wall(engine: str):
            best, result = float("inf"), None
            for _ in range(repeats):
                rack = ShardedRack(num_shards=nsh, engine=engine, **kw)
                t0 = time.perf_counter()
                result = rack.run(trace)
                best = min(best, time.perf_counter() - t0)
            return best, result

        wall_b, rb = best_wall("batched")
        wall_s, rs = best_wall("scalar")
        parity = all(getattr(oracle.stats, f) == getattr(rb.stats, f)
                     and getattr(oracle.stats, f) == getattr(rs.stats, f)
                     for f in STAT_FIELDS)
        hop_total = rs.cross_shard_accesses * hop
        cells.append({
            "num_shards": nsh,
            "scalar_wall_s": wall_s,
            "batched_wall_s": wall_b,
            "scalar_acc_per_s": n / wall_s,
            "batched_acc_per_s": n / wall_b,
            "speedup": wall_s / wall_b,
            "stats_identical_vs_oracle": parity,
            "shard_accesses": rs.shard_accesses,
            "cross_shard_accesses": rs.cross_shard_accesses,
            "hop_us_total": hop_total,
            "runtime_us": {"oracle": oracle.runtime_us,
                           "scalar": rs.runtime_us,
                           "batched": rb.runtime_us},
            "total_thread_us_delta_vs_oracle":
                rs.total_thread_us - oracle.total_thread_us,
            "phases": _phases(rb),
        })
        emit(f"sharded/{nsh}/scalar", wall_s / n * 1e6,
             f"acc_per_s={n / wall_s:.0f};cross={rs.cross_shard_accesses}")
        emit(f"sharded/{nsh}/batched", wall_b / n * 1e6,
             f"acc_per_s={n / wall_b:.0f};"
             f"speedup={wall_s / wall_b:.1f}x;"
             f"parity={'identical' if parity else 'DIVERGED'}")
    out = {
        "workload": "XS (deterministic cross-shard conflicts)",
        "blades": BLADES, "threads_per_blade": THREADS_PER_BLADE,
        "accesses": n,
        "switch_to_switch_us": hop,
        "cells": cells,
    }
    # Export a sample Perfetto trace of the 2-shard batched replay — the
    # CI artifact for eyeballing track layout in ui.perfetto.dev.
    from benchmarks.common import RESULTS
    from repro.telemetry import Telemetry
    from repro.telemetry.exporters import write_perfetto

    # Bounded ring (flight-recorder semantics): full-size cells emit
    # hundreds of thousands of events; the artifact keeps the tail.
    tel = Telemetry(capacity=1 << 15)
    ShardedRack(num_shards=2, engine="batched", telemetry=tel,
                **kw).run(trace)
    trace_path = RESULTS / "trace_sharded.perfetto.json"
    write_perfetto(trace_path, tel, label="bench_sharded/2shard")
    out["perfetto_trace"] = trace_path.name
    print(f"# wrote {trace_path}")
    path = save_json("BENCH_sharded", out)
    print(f"# wrote {path}")
    for c in cells:
        assert c["stats_identical_vs_oracle"], \
            f"{c['num_shards']}-shard cell diverged from the oracle!"
        np.testing.assert_allclose(
            c["total_thread_us_delta_vs_oracle"], c["hop_us_total"],
            rtol=1e-9, err_msg="cross-shard hop accounting drifted")
        if c["speedup"] < 10.0:
            print(f"# WARNING: {c['num_shards']}-shard speedup "
                  f"{c['speedup']:.1f}x below 10x target")
        if perf_floor:
            assert c["speedup"] >= perf_floor, \
                f"{c['num_shards']}-shard cell below {perf_floor}x floor"
    return out


# --------------------------------------------------------------------- #
# ISSUE 7: decentralized control plane + online rebalancing
# (BENCH_rebalance.json).
# --------------------------------------------------------------------- #
def bench_rebalance(quick: bool, perf_floor: float = 0.0,
                    repeats: int = 2) -> dict:
    """Skewed XS cell on a 2-shard rack with per-shard SRAM budgets: the
    private working sets concentrate on shard 0, the online rebalancer
    (threshold 1.5) migrates the hot VA blocks out at the first epoch
    boundary, and the access split flattens.  Reported: pre/post
    shard-access split and SRAM occupancy, migration counts, the exact
    charged migration microseconds, and the batched-vs-scalar replay
    speedup with the rebalancer live (must match stats and migration
    reports exactly)."""
    from repro.core.emulator import ShardedRack

    threads = BLADES * THREADS_PER_BLADE
    per_thread = 500 if quick else 2000
    trace = T.sharded_conflict_trace(
        num_threads=threads, accesses_per_thread=per_thread,
        num_shards=4, blocks_per_shard=2, conflict_frac=0.5,
        write_frac=0.3, hot_pages_per_block=24,
        private_kb_per_thread=256, seed=42)
    kw = dict(system="mind", num_compute_blades=BLADES,
              threads_per_blade=THREADS_PER_BLADE, splitting_enabled=False,
              epoch_us=2500.0, shard_slot_budgets=4096)
    n = len(trace)

    def make(engine: str, rebalance: bool) -> ShardedRack:
        return ShardedRack(
            num_shards=2, engine=engine,
            rebalance_threshold=1.5 if rebalance else None, **kw)

    # Pre-rebalance (skewed) baseline.
    base_rack = make("scalar", rebalance=False)
    base = base_rack.run(trace)
    pre_acc = base.shard_accesses
    pre_occ = base_rack.shard_occupancy()
    pre_frac = max(pre_acc) / sum(pre_acc)

    make("batched", rebalance=True).run(trace)  # jit warm-up (per-process)

    def best_wall(engine: str):
        best, rack, result = float("inf"), None, None
        for _ in range(repeats):
            rack = make(engine, rebalance=True)
            t0 = time.perf_counter()
            result = rack.run(trace)
            best = min(best, time.perf_counter() - t0)
        return best, rack, result

    wall_b, _, rb = best_wall("batched")
    wall_s, rack_s, rs = best_wall("scalar")
    fields = STAT_FIELDS + ("evicted_dirty", "evicted_clean")
    parity = all(getattr(rs.stats, f) == getattr(rb.stats, f)
                 for f in fields)
    post_acc = rs.shard_accesses
    post_occ = rack_s.shard_occupancy()
    post_frac = max(post_acc) / sum(post_acc)
    moves = [m for rp in rs.rebalance_reports for m in rp["moves"]]
    out = {
        "workload": "XS (skewed private blocks, 2-shard rack)",
        "blades": BLADES, "threads_per_blade": THREADS_PER_BLADE,
        "accesses": n,
        "num_shards": 2,
        "shard_slot_budgets": kw["shard_slot_budgets"],
        "rebalance_threshold": 1.5,
        "pre_rebalance": {"shard_accesses": pre_acc,
                          "shard_occupancy": pre_occ,
                          "max_shard_frac": pre_frac},
        "post_rebalance": {"shard_accesses": post_acc,
                           "shard_occupancy": post_occ,
                           "max_shard_frac": post_frac},
        "migrations": len(moves),
        "migrated_entries": sum(m["entries"] for m in moves),
        "migration_us_total":
            sum(rp["migration_us"] for rp in rs.rebalance_reports),
        "rebalance_reports": rs.rebalance_reports,
        "scalar_wall_s": wall_s,
        "batched_wall_s": wall_b,
        "scalar_acc_per_s": n / wall_s,
        "batched_acc_per_s": n / wall_b,
        "speedup_batched_vs_scalar": wall_s / wall_b,
        "stats_identical": parity,
        "reports_identical": rs.rebalance_reports == rb.rebalance_reports,
        "runtime_us": {"scalar": rs.runtime_us, "batched": rb.runtime_us},
        "phases": _phases(rb),
    }
    emit("rebalance/scalar", wall_s / n * 1e6,
         f"acc_per_s={n / wall_s:.0f};moves={len(moves)}")
    emit("rebalance/batched", wall_b / n * 1e6,
         f"acc_per_s={n / wall_b:.0f};speedup={wall_s / wall_b:.1f}x;"
         f"parity={'identical' if parity else 'DIVERGED'};"
         f"split={pre_frac:.0%}->{post_frac:.0%}")
    path = save_json("BENCH_rebalance", out)
    print(f"# wrote {path}")
    assert parity, "rebalance cell coherence stats diverged!"
    assert out["reports_identical"], "migration reports diverged!"
    assert moves, "rebalancer never fired on the skewed cell"
    assert post_frac < pre_frac, \
        "rebalancing did not flatten the shard-access split"
    if out["speedup_batched_vs_scalar"] < 10.0:
        print(f"# WARNING: rebalance-cell speedup "
              f"{out['speedup_batched_vs_scalar']:.1f}x below 10x target")
    if perf_floor:
        assert out["speedup_batched_vs_scalar"] >= perf_floor, \
            f"rebalance cell below {perf_floor}x floor"
    return out


# --------------------------------------------------------------------- #
# ISSUE 8: baseline batched replays (BENCH_baselines.json).
# --------------------------------------------------------------------- #
def bench_baselines(quick: bool, perf_floor: float = 0.0,
                    repeats: int = 2) -> dict:
    """GAM / FastSwap batched replay vs their scalar oracles — the two
    fig6 baseline cells the sweeps were stuck single-stepping before
    ISSUE 8.  GAM runs the invalidation-heavy GC trace (the software-DSM
    worst case: every sharing miss walks the page directory and
    invalidates per blade in the scalar loop) and FastSwap the TF trace.
    Stats, modeled runtime and latency breakdown must be *identical*
    (bytewise float parity is the engine contract) and each cell's
    speedup must clear the 5x target."""
    from repro.dataplane.baselines import BASELINE_PHASES

    per_thread = 400 if quick else 2000
    fields = STAT_FIELDS + ("evicted_dirty", "evicted_clean")
    cells = []
    for system, wl in (("gam", "GC"), ("fastswap", "TF")):
        trace = T.WORKLOADS[wl](
            num_threads=BLADES * THREADS_PER_BLADE,
            accesses_per_thread=per_thread)
        kw = dict(system=system, num_compute_blades=BLADES,
                  threads_per_blade=THREADS_PER_BLADE)
        n = len(trace)

        def best_batched():
            best, result, eng = float("inf"), None, None
            for _ in range(repeats):
                rack = DisaggregatedRack(engine="batched", **kw)
                eng = rack.model.make_batched_engine()
                t0 = time.perf_counter()
                result = eng.run(trace)
                best = min(best, time.perf_counter() - t0)
            return best, result, eng

        def best_scalar():
            best, result = float("inf"), None
            for _ in range(repeats):
                rack = DisaggregatedRack(engine="scalar", **kw)
                t0 = time.perf_counter()
                result = rack.run(trace)
                best = min(best, time.perf_counter() - t0)
            return best, result

        wall_b, rb, eng = best_batched()
        wall_s, rs = best_scalar()
        identical = (
            all(getattr(rs.stats, f) == getattr(rb.stats, f)
                for f in fields)
            and rs.runtime_us == rb.runtime_us
            and rs.latency_breakdown_us == rb.latency_breakdown_us)
        assert set(rb.phase_times) == set(BASELINE_PHASES), \
            f"phase_times drifted: {sorted(rb.phase_times)}"
        cells.append({
            "system": system,
            "workload": wl,
            "blades": BLADES, "threads_per_blade": THREADS_PER_BLADE,
            "accesses": n,
            "scalar_wall_s": wall_s,
            "batched_wall_s": wall_b,
            "scalar_acc_per_s": n / wall_s,
            "batched_acc_per_s": n / wall_b,
            "speedup": wall_s / wall_b,
            "stats_identical": identical,
            "vectorized_accesses": eng.vectorized_accesses,
            "walked_accesses": eng.walked_accesses,
            "runtime_us": {"scalar": rs.runtime_us,
                           "batched": rb.runtime_us},
            "phases": {k: round(rb.phase_times[k], 5)
                       for k in BASELINE_PHASES},
        })
        emit(f"baselines/{system}_{wl}/scalar", wall_s / n * 1e6,
             f"acc_per_s={n / wall_s:.0f}")
        emit(f"baselines/{system}_{wl}/batched", wall_b / n * 1e6,
             f"acc_per_s={n / wall_b:.0f};speedup={wall_s / wall_b:.1f}x;"
             f"parity={'identical' if identical else 'DIVERGED'}")
    out = {"cells": cells}
    path = save_json("BENCH_baselines", out)
    print(f"# wrote {path}")
    for c in cells:
        assert c["stats_identical"], \
            f"{c['system']} baseline cell diverged from the scalar oracle!"
        if c["speedup"] < 5.0:
            print(f"# WARNING: {c['system']} baseline speedup "
                  f"{c['speedup']:.1f}x below 5x target")
        if perf_floor:
            assert c["speedup"] >= perf_floor, \
                f"{c['system']} baseline cell below {perf_floor}x floor"
    return out


# --------------------------------------------------------------------- #
# ISSUE 6: the zero-overhead-when-disabled telemetry guard.
# --------------------------------------------------------------------- #
def bench_telemetry_overhead(quick: bool, repeats: int = 3) -> dict:
    """Replay the headline zipfian cell three ways — no telemetry, a
    *disabled* Telemetry attached, an enabled one — at best-of-repeats.
    No-telemetry and disabled-telemetry leave every component hook
    ``None`` and must stay within 5% of each other (asserted by
    ``--overhead-check``); a regression here means work crept in front
    of the ``is None`` gates.  The enabled wall is recorded for trend
    tracking only."""
    from repro.telemetry import Telemetry

    per_thread = 400 if quick else 1500
    trace = T.ma_trace(num_threads=BLADES * THREADS_PER_BLADE,
                       accesses_per_thread=per_thread)
    kw = dict(splitting_enabled=False)
    _rack("batched", **kw).run(trace)  # jit warm-up (per-process cost)

    def one_wall(tel):
        rack = _rack("batched", telemetry=tel, **kw)
        t0 = time.perf_counter()
        rack.run(trace)
        return time.perf_counter() - t0

    # Interleave the configurations within each round (instead of
    # timing each config's repeats back-to-back) so clock/cache drift
    # over the run lands on all three equally; best-of across rounds.
    factories = (lambda: None, lambda: Telemetry(enabled=False), Telemetry)
    walls = [float("inf")] * 3
    for _ in range(repeats):
        for i, f in enumerate(factories):
            walls[i] = min(walls[i], one_wall(f()))
    base, disabled, enabled = walls
    n = len(trace)
    out = {
        "workload": "M_A (zipfian YCSB-A), batched replay",
        "accesses": n,
        "repeats": repeats,
        "baseline_wall_s": base,
        "disabled_wall_s": disabled,
        "enabled_wall_s": enabled,
        "disabled_overhead_frac": disabled / base - 1.0,
        "enabled_overhead_frac": enabled / base - 1.0,
    }
    emit("telemetry/baseline", base / n * 1e6,
         f"acc_per_s={n / base:.0f}")
    emit("telemetry/disabled", disabled / n * 1e6,
         f"overhead={out['disabled_overhead_frac']:+.1%}")
    emit("telemetry/enabled", enabled / n * 1e6,
         f"overhead={out['enabled_overhead_frac']:+.1%}")
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small trace for CI smoke runs")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--perf-floor", type=float, default=0.0,
                    help="assert every cell's speedup >= this floor "
                         "(0 = warnings only; CI smoke uses 2)")
    ap.add_argument("--overhead-check", action="store_true",
                    help="measure telemetry overhead on the headline cell "
                         "and assert disabled-telemetry <= 5% over baseline")
    ap.add_argument("--only", choices=["all", "dataplane", "eviction",
                                       "cache", "sharded", "rebalance",
                                       "baselines"],
                    default="all",
                    help="run one section in a fresh process (long "
                         "single-process runs can throttle and skew "
                         "late cells)")
    args, _ = ap.parse_known_args()  # tolerate benchmarks.run's own flags
    per_thread = 400 if args.quick else 2000
    repeats = args.repeats or (1 if args.quick else 2)

    if args.only == "eviction":
        bench_eviction(args.quick, args.perf_floor)
        return
    if args.only == "cache":
        bench_cache_eviction(args.quick, args.perf_floor, repeats)
        return
    if args.only == "sharded":
        bench_sharded(args.quick, args.perf_floor, repeats)
        return
    if args.only == "rebalance":
        bench_rebalance(args.quick, args.perf_floor, repeats)
        return
    if args.only == "baselines":
        bench_baselines(args.quick, args.perf_floor, repeats)
        return

    trace = T.ma_trace(num_threads=BLADES * THREADS_PER_BLADE,
                       accesses_per_thread=per_thread)
    rows = [
        bench_config(trace, "zipfian_dataplane_only", repeats,
                     splitting_enabled=False),
        # Epoch boundaries are exact since ISSUE 2, so the paper-style
        # epoch setting must be stat-identical too — and fast since
        # ISSUE 4 (speculate-and-truncate chunking).
        bench_config(trace, "zipfian_100ms_epochs", repeats,
                     epoch_us=100_000.0),
    ]
    headline = rows[0]
    epoch_cell = rows[1]
    out = {
        "blades": BLADES,
        "threads_per_blade": THREADS_PER_BLADE,
        "workload": "M_A (zipfian YCSB-A)",
        "accesses": headline["accesses"],
        "scalar_acc_per_s": headline["scalar_acc_per_s"],
        "batched_acc_per_s": headline["batched_acc_per_s"],
        "speedup": headline["speedup"],
        "stats_identical": headline["stats_identical"],
        "configs": rows,
    }
    if args.overhead_check:
        out["telemetry_overhead"] = bench_telemetry_overhead(
            args.quick, max(repeats, 3))
    path = save_json("BENCH_dataplane", out)
    print(f"# wrote {path}")
    if args.overhead_check:
        frac = out["telemetry_overhead"]["disabled_overhead_frac"]
        assert frac <= 0.05, \
            f"disabled-telemetry overhead {frac:+.1%} exceeds the 5% contract"
    assert headline["stats_identical"], "coherence stats diverged!"
    assert epoch_cell["stats_identical"], "epoch-cell stats diverged!"
    if headline["speedup"] < 10.0:
        print(f"# WARNING: speedup {headline['speedup']:.1f}x below 10x target")
    if epoch_cell["speedup"] < 8.0:
        print(f"# WARNING: epoch-cell speedup "
              f"{epoch_cell['speedup']:.1f}x below 8x target")
    if args.perf_floor:
        assert headline["speedup"] >= args.perf_floor, \
            f"headline below {args.perf_floor}x floor"
        assert epoch_cell["speedup"] >= args.perf_floor, \
            f"epoch cell below {args.perf_floor}x floor"
    if args.only == "all":
        bench_eviction(args.quick, args.perf_floor)
        bench_cache_eviction(args.quick, args.perf_floor, repeats)
        bench_sharded(args.quick, args.perf_floor, repeats)
        bench_rebalance(args.quick, args.perf_floor, repeats)
        bench_baselines(args.quick, args.perf_floor, repeats)


if __name__ == "__main__":
    main()
