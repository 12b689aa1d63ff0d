"""Fig. 8: (left) per-transition latency, (center) throughput vs
read/sharing ratio, (right) latency breakdown vs read ratio x blades."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, engine_from_argv, save_json
from repro.compile_cache import enable_compile_cache
from repro.core.cache import BladePageCache
from repro.core.coherence import CoherenceEngine
from repro.core.directory import CacheDirectory
from repro.core.emulator import DisaggregatedRack
from repro.core.network_model import NetworkModel
from repro.core.traces import uniform_trace
from repro.core.types import AccessType, MemAccess

BASE = 1 << 40


def transition_latencies():
    """Fig. 8 (left): every MSI transition's end-to-end latency, for 2-8
    requesting blades."""
    rows = []
    for nblades in (2, 4, 8):
        d = CacheDirectory()
        caches = {b: BladePageCache(b, 1 << 20) for b in range(nblades)}
        e = CoherenceEngine(d, caches)
        net = NetworkModel()

        def lat(blade, write):
            acts, rec = e.access(MemAccess(
                blade, 1, BASE, AccessType.WRITE if write else AccessType.READ))
            return rec.kind, net.latency(acts, rec).total_us

        # I->S
        k, us = lat(0, False)
        rows.append({"blades": nblades, "transition": k, "us": us})
        # S->S (all blades join)
        for b in range(1, nblades):
            k, us = lat(b, False)
        rows.append({"blades": nblades, "transition": "S->S", "us": us})
        # S->M (invalidate nblades-1 sharers, parallel)
        k, us = lat(0, True)
        rows.append({"blades": nblades, "transition": k, "us": us})
        # M->M from another blade (sequential)
        k, us = lat(1, True)
        rows.append({"blades": nblades, "transition": k, "us": us})
        # M->S (sequential flush)
        k, us = lat(2 % nblades, False)
        rows.append({"blades": nblades, "transition": k, "us": us})
    for r in rows:
        emit(f"fig8_left/{r['transition']}/b{r['blades']}", r["us"], "")
    return rows


def throughput_grid(engine="scalar"):
    """Fig. 8 (center): memory throughput vs read ratio x sharing ratio."""
    rows = []
    for read_ratio in (0.0, 0.5, 1.0):
        for sharing in (0.0, 0.5, 1.0):
            t0 = time.perf_counter()
            rack = DisaggregatedRack("mind", num_compute_blades=8,
                                     threads_per_blade=1, engine=engine)
            tr = uniform_trace(8, read_ratio, sharing,
                               accesses_per_thread=400,
                               working_set_pages=40_000)
            r = rack.run(tr)
            wall = (time.perf_counter() - t0) * 1e6
            iops = r.performance * 1e6  # accesses/us -> IOPS
            rows.append({"read_ratio": read_ratio, "sharing": sharing,
                         "iops": iops, "engine_used": r.engine})
            emit(f"fig8_center/R{read_ratio}/S{sharing}", wall,
                 f"iops={iops:.2e}")
    return rows


def latency_breakdown(engine="scalar"):
    """Fig. 8 (right): end-to-end latency components at sharing=1."""
    rows = []
    for read_ratio in (0.0, 0.5, 1.0):
        for nb in (2, 4, 8):
            rack = DisaggregatedRack("mind", num_compute_blades=nb,
                                     threads_per_blade=1, engine=engine)
            tr = uniform_trace(nb, read_ratio, 1.0, accesses_per_thread=400,
                               working_set_pages=40_000)
            r = rack.run(tr)
            n = max(1, r.stats.accesses)
            bd = {k: v / n for k, v in r.latency_breakdown_us.items()}
            mean_us = r.mean_access_us  # busy thread-time per access
            rows.append({"read_ratio": read_ratio, "blades": nb,
                         "mean_us": mean_us, "engine_used": r.engine, **bd})
            emit(f"fig8_right/R{read_ratio}/b{nb}", mean_us,
                 f"fetch={bd['fetch']:.1f};tlb={bd['tlb']:.2f};"
                 f"queue={bd['queue']:.2f}")
    return rows


def main() -> None:
    enable_compile_cache()
    choice = engine_from_argv()
    out = {
        "engine": choice.engine,
        "left": transition_latencies(),
        "center": throughput_grid(engine=choice.engine),
        "right": latency_breakdown(engine=choice.engine),
    }
    save_json("fig8_latency", out)


if __name__ == "__main__":
    main()
