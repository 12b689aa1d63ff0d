"""MIND and its simulated variants (§7.1): the in-network MMU systems.

``mind`` is the full switch-centric design under TSO; ``mind-pso``
relaxes remote writes to PSO (asynchronous retirement — only the issue
cost and target queueing are exposed); ``mind-pso+`` additionally gives
the switch an infinite directory (the rack constructor widens
``max_directory_entries`` before the MMU is built).
"""

from __future__ import annotations

from benchmarks.chip.reference.system_base import SystemModel
from benchmarks.chip.reference.types import AccessType, MemAccess


class MindModel(SystemModel):
    has_switch = True

    def __init__(self, rack, name: str = "mind"):
        super().__init__(rack)
        self.name = name
        self.pso = name in ("mind-pso", "mind-pso+")

    @property
    def stats(self):
        return self.rack.mmu.engine.stats

    # ------------------------------------------------------------------ #
    def scalar_access(self, blade, vaddr, is_write, breakdown, trans_lat):
        rack = self.rack
        req = MemAccess(
            blade_id=blade,
            pdid=1,
            vaddr=vaddr,
            access=AccessType.WRITE if is_write else AccessType.READ,
        )
        res = rack._route(blade, vaddr, req)
        lb = res.latency
        breakdown["fetch"] += lb.fetch_us
        breakdown["invalidation"] += lb.invalidation_us
        breakdown["tlb"] += lb.tlb_us
        breakdown["queue"] += lb.queue_us
        breakdown["switch"] += lb.switch_us
        breakdown["retry"] += lb.retry_us
        if res.rec is not None:
            trans_lat.setdefault(res.rec.kind, []).append(lb.total_us)
        if self.pso and is_write and not res.acts.hit_local:
            # PSO: the store retires into a write buffer; only issue cost
            # is exposed.  Queueing at invalidation targets persists (the
            # paper's simulation cannot elide it either).
            us = rack.mmu.network.k.switch_pipeline_ns / 1000.0 + lb.queue_us
        else:
            us = lb.total_us
        return us

    def on_epoch(self, next_epoch_at, clocks, breakdown, dir_timeline):
        rack = self.rack
        rack.cp.maybe_run_epoch(now_us=next_epoch_at,
                                split=rack.splitting_enabled)
        dir_timeline.append(rack.mmu.engine.directory.num_entries())
        rack.mmu.network.begin_window()
        mig = rack.cp.take_migration_charge()
        if mig:
            # Migration is stop-the-world: every thread stalls while
            # region state crosses the s2s links.
            clocks += mig
            breakdown["switch"] += mig * len(clocks)

    # ------------------------------------------------------------------ #
