"""Chaos harness (ISSUE 9): seeded fault schedules across replay regimes.

Usage: PYTHONPATH=src python -m benchmarks.chaos_bench [--quick] [--seed N]

Sweeps the fault plane across the replay regimes the parity suite pins
(plain / directory-pressure / cache-pressure / epoch / sharded), on
both engines, with three cells per regime:

* ``faults`` — a seeded blade kill/restore schedule (plus a mid-trace
  switch kill on the sharded regime).  Asserts scalar == batched parity
  under faults *and* exact convergence to the fault-free run (blade
  failures are bookkeeping + accounting, never silent corruption).
* ``lossy`` — a lossy fabric with retry/backoff.  Asserts byte-equal
  scalar/batched runtime and stats for the same ``fabric_seed`` (the
  retry draw is a counter-based hash both engines share).
* ``chaos`` — both at once.  Asserts parity and a clean
  :func:`repro.telemetry.check_invariants` replay of both streams.

Every cell also replays its flight-recorder stream through the
coherence invariant checker.  Results (per-cell runtimes, retry/fault
accounting, wall-clock per engine) land in
``benchmarks/results/BENCH_chaos.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import repro.core.traces as T
from benchmarks.common import save_json
from repro.compile_cache import enable_compile_cache
from repro.core import faults as flt
from repro.core.emulator import DisaggregatedRack, ShardedRack
from repro.core.types import NetworkConstants
from repro.telemetry import Telemetry, canonical, check_invariants

#: Lossy-fabric constants for the ``lossy``/``chaos`` cells: loss high
#: enough that every regime retransmits and the chatty regimes also
#: exhaust the retry budget (timeout probability is loss^(retries+1)).
FABRIC = dict(fabric_loss_prob=0.25, fabric_timeout_us=12.0,
              fabric_backoff=2.0, fabric_timeout_cap_us=96.0,
              fabric_max_retries=3)


def chaos_schedule(rng, n: int, blades, cycles: int):
    """A seeded, valid blade kill/restore schedule: ``cycles`` repeated
    kill -> restore pairs at distinct sorted indexes (never more than
    one blade dead at a time, so any surviving blade can absorb the
    re-homed vmas)."""
    idxs = np.sort(rng.choice(np.arange(1, n - 1), size=2 * cycles,
                              replace=False))
    events = []
    for c in range(cycles):
        b = int(rng.choice(blades))
        events.append(flt.FaultEvent(int(idxs[2 * c]), flt.BLADE_KILL, b))
        events.append(flt.FaultEvent(int(idxs[2 * c + 1]),
                                     flt.BLADE_RESTORE, b))
    return events


def regimes(quick: bool):
    per = 400 if quick else 1500
    tf = T.tf_trace(num_threads=4, accesses_per_thread=per, seed=3)
    sh = T.sharded_conflict_trace(num_threads=4,
                                  accesses_per_thread=per, num_shards=4,
                                  blocks_per_shard=2, seed=9)
    base = dict(system="mind", num_compute_blades=2, threads_per_blade=2)
    return [
        ("plain", tf, dict(base, splitting_enabled=False)),
        ("dir_pressure", tf, dict(base, splitting_enabled=False,
                                  max_directory_entries=120)),
        ("cache_pressure", tf, dict(base, splitting_enabled=False,
                                    cache_bytes_per_blade=1 << 14)),
        ("epoch", tf, dict(base, splitting_enabled=True,
                           epoch_us=4000.0)),
        ("sharded", sh, dict(base, num_shards=2,
                             splitting_enabled=False)),
    ]


def build(kw, engine, constants=None):
    kw = dict(kw)
    sharded = "num_shards" in kw
    cls = ShardedRack if sharded else DisaggregatedRack
    return cls(engine=engine, constants=constants, telemetry=Telemetry(),
               durable_writebacks=True, **kw)


def assert_parity(rs, rb, ctx: str) -> None:
    if rs.stats != rb.stats:
        raise SystemExit(f"fatal [{ctx}]: scalar/batched stats diverge\n"
                         f"  scalar:  {rs.stats}\n  batched: {rb.stats}")
    if rs.runtime_us != rb.runtime_us or \
            rs.total_thread_us != rb.total_thread_us:
        raise SystemExit(
            f"fatal [{ctx}]: runtime diverges — scalar {rs.runtime_us} "
            f"vs batched {rb.runtime_us}")
    for key in rs.latency_breakdown_us:
        np.testing.assert_allclose(
            rs.latency_breakdown_us[key], rb.latency_breakdown_us[key],
            rtol=1e-9, err_msg=f"[{ctx}] breakdown[{key}]")
    es = [e.key() for e in canonical(rs.telemetry.recorder.events)]
    eb = [e.key() for e in canonical(rb.telemetry.recorder.events)]
    if es != eb:
        raise SystemExit(f"fatal [{ctx}]: event streams diverge "
                         f"({len(es)} vs {len(eb)} events)")
    if rs.fault_reports != rb.fault_reports:
        raise SystemExit(f"fatal [{ctx}]: fault reports diverge\n"
                         f"  scalar:  {rs.fault_reports}\n"
                         f"  batched: {rb.fault_reports}")


def assert_clean(res, ctx: str) -> None:
    v = check_invariants(res.telemetry)
    if v:
        raise SystemExit(f"fatal [{ctx}]: {len(v)} coherence invariant "
                         f"violation(s), first: {v[0]}")


def run_cell(name: str, trace, kw, schedule=None, constants=None) -> dict:
    out = {"regime": name}
    results = {}
    for engine in ("scalar", "batched"):
        rack = build(kw, engine, constants)
        if schedule is not None:
            # The same schedule object feeds both engines — the fault
            # plan is part of the cell, not of one rack.
            rack.schedule_fault_plan(schedule)
        t0 = time.perf_counter()
        results[engine] = rack.run(trace)
        out[f"wall_s_{engine}"] = round(time.perf_counter() - t0, 4)
    rs, rb = results["scalar"], results["batched"]
    assert_parity(rs, rb, name)
    assert_clean(rs, f"{name}/scalar")
    assert_clean(rb, f"{name}/batched")
    out.update(
        accesses=rs.stats.accesses,
        runtime_us=rs.runtime_us,
        retry_us=rs.latency_breakdown_us.get("retry", 0.0),
        retries=int(rs.telemetry.metrics.total("fabric_retries_total")),
        timeouts=int(rs.telemetry.metrics.total("fabric_timeouts_total")),
        fault_reports=[dataclasses.asdict(r) for r in rs.fault_reports],
        speedup=(round(out["wall_s_scalar"] / out["wall_s_batched"], 2)
                 if out["wall_s_batched"] > 0 else None),
    )
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small traces (the CI smoke configuration)")
    ap.add_argument("--seed", type=int, default=2107,
                    help="seed for the fault schedules")
    args = ap.parse_args()

    cells = []
    for name, trace, kw in regimes(args.quick):
        n = len(trace)
        rng = np.random.default_rng(args.seed)
        cycles = 2 if args.quick else 3
        blades = sorted(build(kw, "scalar").allocator.blades)
        sched = chaos_schedule(rng, n, blades, cycles)
        if name == "sharded":
            used = {e.index for e in sched}
            i = next(j for j in range(n // 2, n) if j not in used)
            sched.append(flt.FaultEvent(i, flt.SWITCH_KILL, 1))

        # Convergence reference: the fault-free run.
        base = run_cell(name, trace, kw)

        cell = run_cell(name, trace, kw, schedule=sched)
        cell["cell"] = "faults"
        if cell["runtime_us"] != base["runtime_us"]:
            raise SystemExit(
                f"fatal [{name}/faults]: fault replay did not converge — "
                f"{cell['runtime_us']} vs fault-free {base['runtime_us']}")
        cells.append(cell)
        print(f"{name}/faults: runtime {cell['runtime_us']:.1f} us "
              f"(== fault-free), {len(cell['fault_reports'])} faults, "
              f"speedup {cell['speedup']}x")

        k = NetworkConstants(fabric_seed=args.seed, **FABRIC)
        cell = run_cell(name, trace, kw, constants=k)
        cell["cell"] = "lossy"
        if cell["retries"] == 0:
            raise SystemExit(f"fatal [{name}/lossy]: fabric drew no "
                             "retransmissions — dead knob?")
        cells.append(cell)
        print(f"{name}/lossy: {cell['retries']} retries "
              f"({cell['timeouts']} timeouts), retry charge "
              f"{cell['retry_us']:.1f} us, speedup {cell['speedup']}x")

        cell = run_cell(name, trace, kw, schedule=sched, constants=k)
        cell["cell"] = "chaos"
        cells.append(cell)
        print(f"{name}/chaos: runtime {cell['runtime_us']:.1f} us, "
              f"{len(cell['fault_reports'])} faults, "
              f"{cell['retries']} retries, speedup {cell['speedup']}x")

    path = save_json("BENCH_chaos", {
        "bench": "chaos", "quick": args.quick, "seed": args.seed,
        "fabric": FABRIC, "cells": cells,
    })
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
