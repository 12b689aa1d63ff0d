"""Fig. 6: performance scaling, intra-blade (left) and inter-blade (right).

MIND / MIND-PSO / GAM / FastSwap on TF, GC, M_A, M_C traces; performance
= inverse runtime normalized to MIND at 1 thread (left) / 1 blade (right).

With ``--engine batched`` every cell replays through a vectorized
engine (no scalar fallback unless ``--allow-scalar-fallback``), records
which engine actually ran as ``engine_used``, and cross-checks each
batched cell against a fresh scalar-oracle run: stats and modeled
runtime must match exactly or the benchmark aborts.
"""

from __future__ import annotations

import time

from benchmarks.common import (EngineChoice, emit, engine_from_argv,
                               save_json, run_workload_with_engine)
from repro.compile_cache import enable_compile_cache

ACCESSES = 500


def _cell(engine, system, wl, **kw):
    """Run one fig6 cell; returns (result, engine_used, parity_checked)."""
    r = run_workload_with_engine(engine, system, wl, **kw)
    parity = False
    if r.engine == "batched":
        from repro.core.emulator import run_workload

        ref = run_workload(system, wl, **kw)
        if r.stats != ref.stats or r.runtime_us != ref.runtime_us:
            raise SystemExit(
                f"fatal: batched/{system}/{wl} diverged from the scalar "
                f"oracle: stats {r.stats} vs {ref.stats}, runtime "
                f"{r.runtime_us} vs {ref.runtime_us}")
        parity = True
    return r, r.engine, parity


def intra_blade(workloads=("TF", "GC"), threads=(1, 4, 10),
                engine="scalar"):
    rows = []
    for wl in workloads:
        base = None
        for th in threads:
            for system in ("mind", "gam", "fastswap"):
                t0 = time.perf_counter()
                r, used, parity = _cell(
                    engine, system, wl, num_compute_blades=1,
                    threads_per_blade=th, accesses_per_thread=ACCESSES)
                wall = (time.perf_counter() - t0) * 1e6
                if system == "mind" and th == threads[0]:
                    base = r.performance
                norm = r.performance / base
                rows.append({"workload": wl, "threads": th, "system": system,
                             "perf_norm": norm, "engine_used": used,
                             "parity_checked": parity})
                emit(f"fig6_intra/{wl}/{system}/t{th}", wall,
                     f"perf_norm={norm:.2f};engine={used}")
    return rows


def inter_blade(workloads=("TF", "GC", "M_A", "M_C"), blades=(1, 2, 4, 8),
                threads=4, engine="scalar"):
    rows = []
    for wl in workloads:
        base = None
        for nb in blades:
            for system in ("mind", "mind-pso", "mind-pso+", "gam"):
                t0 = time.perf_counter()
                r, used, parity = _cell(
                    engine, system, wl, num_compute_blades=nb,
                    threads_per_blade=threads, accesses_per_thread=ACCESSES)
                wall = (time.perf_counter() - t0) * 1e6
                if system == "mind" and nb == blades[0]:
                    base = r.performance
                norm = r.performance / base
                rows.append({"workload": wl, "blades": nb, "system": system,
                             "perf_norm": norm,
                             "invalidations": r.stats.invalidations,
                             "false_inv": r.stats.false_invalidated_pages,
                             "engine_used": used,
                             "parity_checked": parity})
                emit(f"fig6_inter/{wl}/{system}/b{nb}", wall,
                     f"perf_norm={norm:.2f};engine={used}")
    return rows


def main() -> None:
    enable_compile_cache()
    choice = engine_from_argv()
    intra = intra_blade(engine=choice)
    inter = inter_blade(engine=choice)
    fallbacks = sum(1 for row in intra + inter
                    if choice.engine == "batched"
                    and row["engine_used"] != "batched")
    rows = {"engine": choice.engine,
            "allow_scalar_fallback": choice.allow_scalar_fallback,
            "scalar_fallbacks": fallbacks,
            "intra": intra, "inter": inter}
    save_json("fig6_scaling", rows)


if __name__ == "__main__":
    main()
