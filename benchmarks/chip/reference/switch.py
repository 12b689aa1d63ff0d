"""The staged switch data-plane pipeline (§3.2, §6.3).

Models the ingress pipeline order of the MIND switch program:

    parse -> [protection match] -> [translation match] -> [directory MAU 1:
    lookup] -> [MAU 2: materialized transition table] -> (recirculate:
    directory write-back) -> egress multicast w/ sharer filter.

Protection and translation run in PARALLEL in the real ASIC (§3.2 "In
parallel, the data plane also ensures the requesting process has
permissions"); we model that by charging a single pipeline traversal.

This module is the *behavioural* model used by the emulator and tests; the
batched JAX/Pallas realization of stages lives in kernels/range_match.py
and kernels/directory_msi.py, and ``export_dataplane_tables`` below is the
bridge that materializes match-action tables for those kernels.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmarks.chip.reference.address_space import GlobalAddressSpace
from benchmarks.chip.reference.coherence import CoherenceEngine, TransitionRecord
from benchmarks.chip.reference.network_model import LatencyBreakdown, NetworkModel
from benchmarks.chip.reference.protection import ProtectionTable
from benchmarks.chip.reference.types import CoherenceActions, MemAccess


@dataclass
class SwitchResult:
    acts: CoherenceActions
    rec: TransitionRecord | None
    latency: LatencyBreakdown
    target_blade: int = -1  # memory blade after translation (if fetched)
    paddr: int = -1


class InNetworkMMU:
    """Ties the stages together; one instance == one programmable switch."""

    def __init__(
        self,
        gas: GlobalAddressSpace,
        protection: ProtectionTable,
        engine: CoherenceEngine,
        network: NetworkModel,
    ):
        self.gas = gas
        self.protection = protection
        self.engine = engine
        self.network = network

    # ------------------------------------------------------------------ #
    def handle(self, req: MemAccess) -> SwitchResult:
        # Stage A (parallel in ASIC): protection check.
        if not self.protection.check(req.pdid, req.vaddr, req.access):
            acts = CoherenceActions(fault="protection")
            self.engine.stats.faults += 1
            sw_us = self.network.k.switch_pipeline_ns / 1000.0
            return SwitchResult(acts, None, LatencyBreakdown(switch_us=sw_us))

        # Stage B: coherence (directory MAUs).  The directory decides
        # whether a fetch is needed and from where.
        acts, rec = self.engine.access(req)

        # Stage C: translation — only exercised when the request leaves the
        # switch toward a memory blade (fetch_from_memory).
        target, paddr = -1, -1
        if acts.fetch_from_memory:
            target, paddr = self.gas.translate(req.vaddr)

        lat = self.network.latency(acts, rec)
        return SwitchResult(acts, rec, lat, target, paddr)

    # ------------------------------------------------------------------ #

def make_mmu(
    num_memory_blades: int,
    num_compute_blades: int,
    cache_bytes_per_blade: int,
    max_directory_entries: int = 30_000,
    initial_region_log2: int = 14,
    max_region_log2: int = 21,
    downgrade_keeps_copy: bool = False,
    directory_eviction: str = "lru",
    blade_capacity: int | None = None,
):
    """Convenience factory wiring a full single-switch MIND instance.

    ``blade_capacity`` shrinks each memory
    blade below its full VA span (allocation-pressure benchmarks)."""
    from benchmarks.chip.reference.allocator import MemoryAllocator
    from benchmarks.chip.reference.cache import BladePageCache
    from benchmarks.chip.reference.directory import CacheDirectory
    from benchmarks.chip.reference.types import SwitchResources

    gas = GlobalAddressSpace()
    for _ in range(num_memory_blades):
        gas.add_blade(blade_capacity)
    alloc = MemoryAllocator(gas)
    prot = ProtectionTable()
    directory = CacheDirectory(
        max_region_log2=max_region_log2,
        initial_region_log2=initial_region_log2,
        resources=SwitchResources(max_directory_entries=max_directory_entries),
        eviction=directory_eviction,
    )
    caches = {
        b: BladePageCache(b, cache_bytes_per_blade) for b in range(num_compute_blades)
    }
    engine = CoherenceEngine(directory, caches, downgrade_keeps_copy=downgrade_keeps_copy)
    mmu = InNetworkMMU(gas, prot, engine, NetworkModel())
    return mmu, alloc
