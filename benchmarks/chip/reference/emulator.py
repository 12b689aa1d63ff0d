"""The benchmark's plain reference: MIND's scalar rack emulator.

A copy of the repository's scalar emulator (the exact oracle the
batched engine is held to), cut to the single-switch ``mind`` system
and imported from nothing of the program, so that no change to the
program can move the reference.  Each emulated thread owns a logical
clock that the per-access latency of the network model advances;
``clock_dtype`` is the precision of those clocks (float64, as the
configurations state; the benchmark's control runs them in float32).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip.reference import faults as flt
from benchmarks.chip.reference.control_plane import ControlPlane
from benchmarks.chip.reference.network_model import NetworkModel
from benchmarks.chip.reference.switch import make_mmu
from benchmarks.chip.reference.mind import MindModel
from benchmarks.chip.reference.types import (
    PAGE_SIZE,
    EpochStats,
    MemAccess,
    NetworkConstants,
    Perm,
)


@dataclass
class EmulationResult:
    system: str
    workload: str
    num_blades: int
    threads_per_blade: int
    runtime_us: float
    performance: float  # accesses per us (inverse runtime x accesses)
    stats: EpochStats
    directory_timeline: list[int] = field(default_factory=list)
    epoch_reports: list = field(default_factory=list)
    latency_breakdown_us: dict[str, float] = field(default_factory=dict)
    transition_latencies: dict[str, list[float]] = field(default_factory=dict)
    total_thread_us: float = 0.0  # sum of all per-thread clock time
    engine: str = "scalar"  # which data-plane engine produced this result
    # Wall-clock seconds per engine phase (batched engine only): host
    # pre-passes / scheduling / device replay / latency reconstruction /
    # epoch control — the per-phase perf trajectory BENCH_*.json tracks.
    phase_times: dict = field(default_factory=dict)
    # Online shard rebalancing (decentralized control plane): one report
    # per epoch that migrated blocks, with the migrated entry count and
    # the stop-the-world switch-to-switch latency charged.
    rebalance_reports: list = field(default_factory=list)
    # Fault plane (repro.core.faults): one FaultReport per fired fault
    # (switch kills, blade kills/restores) in firing order.  Accounting
    # lives here, outside EpochStats, so faulted replays converge to
    # the fault-free run's coherence statistics.
    fault_reports: list = field(default_factory=list)


class DisaggregatedRack:
    """One emulated rack: N compute blades x M memory blades + switch."""

    def __init__(
        self,
        system: str = "mind",
        num_compute_blades: int = 1,
        threads_per_blade: int = 10,
        num_memory_blades: int = 8,
        cache_bytes_per_blade: int = 512 << 20,  # 512 MB, ~25% of footprint (§7)
        max_directory_entries: int = 30_000,
        initial_region_log2: int = 14,  # 16 KB (§7)
        max_region_log2: int = 21,  # 2 MB
        epoch_us: float = 10_000.0,
        splitting_enabled: bool = True,
        constants: NetworkConstants | None = None,
        downgrade_keeps_copy: bool = False,
        gam_sw_cores: int = 4,
        clock_dtype=np.float64,
        directory_eviction: str = "lru",
        durable_writebacks: bool = False,
    ):
        if not system.startswith("mind"):
            raise ValueError(f"the reference models mind only, not {system!r}")
        self.system = system
        self.clock_dtype = clock_dtype
        self.nb = num_compute_blades
        self.tpb = threads_per_blade
        self.epoch_us = epoch_us
        self.splitting_enabled = splitting_enabled
        # Fault plane (repro.core.faults): an ordered schedule of
        # FaultEvents, each fired right before its access index is
        # issued (both engines honour exact indexes; the batched engine
        # clamps chunks so none straddles a fault point).  Consumed
        # destructively by the replay.
        self._fault_schedule: list[flt.FaultEvent] = []
        self.fault_reports: list[flt.FaultReport] = []
        # Whether a killed blade's exposed dirty pages can be recovered
        # from a durable backing store (blade-kill accounting only).
        self.durable_writebacks = durable_writebacks
        self.gam_sw_cores = gam_sw_cores
        self.cache_bytes_per_blade = cache_bytes_per_blade
        if system == "mind-pso+":
            max_directory_entries = 10**9  # infinite switch capacity
        self.mmu, self.allocator = make_mmu(
            num_memory_blades=num_memory_blades,
            num_compute_blades=num_compute_blades,
            cache_bytes_per_blade=cache_bytes_per_blade,
            max_directory_entries=max_directory_entries,
            initial_region_log2=initial_region_log2,
            max_region_log2=max_region_log2,
            downgrade_keeps_copy=downgrade_keeps_copy,
            directory_eviction=directory_eviction,
        )
        if constants is not None:
            self.mmu.network = NetworkModel(constants)
        self.cp = ControlPlane(self.mmu, self.allocator, epoch_us=epoch_us)
        # The per-system model: owns the system's private state (the
        # in-network MMU path for mind*, the software-DSM directory and
        # blade caches for gam, the per-blade swap caches for fastswap),
        # the PSO flag and the batched-engine choice.
        self.model = MindModel(self, name=system)
        self.cp.prepopulate_on_mmap = self.model.has_switch
        # The lossy fabric of the program is not copied: no configuration
        # arms it, and one that does is refused rather than ignored.
        if self.mmu.network.k.fabric_loss_prob > 0.0:
            raise ValueError("the reference models no lossy fabric")
        # Scalar-loop cursor: the global access index the oracle is
        # replaying (fault firing keys off it).
        self._cur_access = -1

    @property
    def epoch_driver_enabled(self) -> bool:
        """Whether the emulated-time epoch machinery runs: Bounded
        Splitting, and/or the shard rebalancer (which fires at the same
        epoch boundaries even with splitting off)."""
        return self.splitting_enabled or self.cp.rebalance_threshold is not None

    # ------------------------------------------------------------------ #
    def _map_arena(self, trace) -> list[tuple[int, int, int]]:
        """Allocate vmas for the trace arena; returns sorted
        (arena_start, arena_end, vaddr_base) segments."""
        segs: list[tuple[int, int, int]] = []
        pdid = 1
        shared = trace.shared_bytes
        if shared > 0:
            vma = self.cp.sys_mmap(pdid, shared, Perm.RW, requesting_blade=0).vma
            segs.append((0, shared, vma.base))
        priv_total = trace.arena_bytes - shared
        if priv_total > 0:
            nthreads = self.nb * self.tpb
            per = priv_total // nthreads if nthreads else priv_total
            if per > 0:
                for t in range(nthreads):
                    blade = t // self.tpb
                    vma = self.cp.sys_mmap(
                        pdid, per, Perm.RW, requesting_blade=blade
                    ).vma
                    segs.append((shared + t * per, shared + (t + 1) * per, vma.base))
        return sorted(segs)

    def _to_vaddr_batch(self, segs, arena_offs: np.ndarray) -> np.ndarray:
        """Vectorized arena-offset -> vaddr mapping (batched data plane)."""
        starts = np.array([s for s, _, _ in segs], np.int64)
        ends = np.array([e for _, e, _ in segs], np.int64)
        bases = np.array([b for _, _, b in segs], np.int64)
        offs = np.asarray(arena_offs, np.int64)
        idx = np.searchsorted(starts, offs, side="right") - 1
        idx = np.clip(idx, 0, len(segs) - 1)
        # Clamp offsets beyond the covered prefix into the containing /
        # last segment, mirroring the scalar `_to_vaddr` fallback.
        rel = np.minimum(offs - starts[idx], ends[idx] - starts[idx] - 1)
        rel = np.maximum(rel, 0)
        return bases[idx] + rel

    def _to_vaddr(self, segs, arena_off: int) -> int:
        # Binary search over segments.
        lo, hi = 0, len(segs) - 1
        while lo <= hi:
            mid = (lo + hi) // 2
            s, e, base = segs[mid]
            if arena_off < s:
                hi = mid - 1
            elif arena_off >= e:
                lo = mid + 1
            else:
                return base + (arena_off - s)
        # Offsets beyond the last slice (rounding): clamp into last seg.
        s, e, base = segs[-1]
        return base + min(arena_off - s, e - s - 1) if arena_off >= e else segs[0][2]

    # ------------------------------------------------------------------ #
    # Fault plane: schedule faults against exact access indexes.
    # ------------------------------------------------------------------ #
    def schedule_fault_plan(self, events) -> None:
        """Append fault events to the replay schedule.  Validation is
        loud (``ValueError`` naming the offending entry): unknown kinds
        and targets, overlapping indexes and impossible kill/restore
        sequences are rejected here; index-vs-trace-length bounds are
        checked at ``run()`` once the trace is known."""
        merged = sorted(self._fault_schedule + list(events),
                        key=lambda e: e.index)
        flt.validate_fault_plan(self, merged)
        self._fault_schedule = merged

    def _fire_fault(self, ev, written_pages=None):
        """Dispatch one scheduled fault (shared by both engines at the
        exact access index) and record its report."""
        if ev.kind == flt.SWITCH_KILL:
            restored = self.kill_and_restore_switch(ev.target)
            rep = flt.FaultReport(kind=flt.SWITCH_KILL, index=ev.index,
                                  target=ev.target,
                                  entries_restored=restored)
        elif ev.kind == flt.BLADE_KILL:
            rep = flt.kill_memory_blade(self, ev.index, ev.target,
                                        written_pages or set())
        else:
            rep = flt.restore_memory_blade(self, ev.index, ev.target)
        self.fault_reports.append(rep)
        return rep

    # ------------------------------------------------------------------ #
    def run(self, trace, max_accesses: int | None = None) -> EmulationResult:
        if self._fault_schedule:
            n = (len(trace) if max_accesses is None
                 else min(len(trace), max_accesses))
            flt.validate_fault_plan(self, self._fault_schedule, n)
        return self._run_scalar(trace, max_accesses)

    def _run_scalar(self, trace, max_accesses: int | None = None) -> EmulationResult:
        segs = self._map_arena(trace)
        nthreads = self.nb * self.tpb
        clocks = np.zeros(nthreads, self.clock_dtype)
        breakdown = {"fetch": 0.0, "invalidation": 0.0, "tlb": 0.0, "queue": 0.0,
                     "switch": 0.0, "local": 0.0, "software": 0.0,
                     "retry": 0.0}
        trans_lat: dict[str, list[float]] = {}
        dir_timeline: list[int] = []
        n = len(trace) if max_accesses is None else min(len(trace), max_accesses)
        next_epoch_at = self.epoch_us
        sched = self._fault_schedule
        # Blade-kill accounting needs the written-page prefix at the
        # fire index; track it only when the schedule can consume it.
        track_writes = any(ev.kind == flt.BLADE_KILL for ev in sched)
        written: set[int] = set()

        for i in range(n):
            while sched and sched[0].index == i:
                self._fire_fault(sched.pop(0), written_pages=written)
            t = int(trace.threads[i]) % nthreads
            blade = t // self.tpb
            vaddr = self._to_vaddr(segs, int(trace.offsets[i]))
            is_write = bool(trace.ops[i])
            self._cur_access = i
            us = self.model.scalar_access(blade, vaddr, is_write, breakdown,
                                          trans_lat)
            clocks[t] += us
            if track_writes and is_write:
                written.add(vaddr & ~(PAGE_SIZE - 1))

            # Epoch boundary: driven by emulated time (mean thread clock).
            if self.epoch_driver_enabled and clocks.mean() >= next_epoch_at:
                self.model.on_epoch(next_epoch_at, clocks, breakdown,
                                    dir_timeline)
                next_epoch_at += self.epoch_us

        stats = self.model.stats
        runtime = float(clocks.max()) if n else 0.0
        return EmulationResult(
            system=self.system,
            workload=trace.name,
            num_blades=self.nb,
            threads_per_blade=self.tpb,
            runtime_us=runtime,
            performance=(n / runtime) if runtime > 0 else 0.0,
            stats=stats,
            directory_timeline=dir_timeline,
            epoch_reports=list(self.cp.epoch_reports),
            latency_breakdown_us=breakdown,
            transition_latencies=trans_lat,
            total_thread_us=float(clocks.sum()),
            engine="scalar",
            rebalance_reports=list(self.cp.rebalance_reports),
            fault_reports=list(self.fault_reports),
        )

    # ------------------------------------------------------------------ #
    def _route(self, blade: int, vaddr: int, req: MemAccess):
        """Route one packet to its switch.  The single-switch rack has
        exactly one pipeline; :class:`ShardedRack` overrides this with
        home-switch routing plus the cross-shard hop."""
        return self.mmu.handle(req)
