"""Switch control plane (§3.2, §6.3): the "switch CPU" program.

Hosts the mmap intercept, owns the global allocation policy and drives
Bounded Splitting epochs.  The program's failover snapshots and shard
rebalancer are left out of this copy: no configuration uses them.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmarks.chip.reference.allocator import MemoryAllocator
from benchmarks.chip.reference.bounded_splitting import BoundedSplitting, EpochReport
from benchmarks.chip.reference.switch import InNetworkMMU
from benchmarks.chip.reference.types import VMA, Perm


@dataclass
class SyscallResult:
    retval: int
    vma: VMA | None = None


class ControlPlane:
    def __init__(
        self,
        mmu: InNetworkMMU,
        allocator: MemoryAllocator,
        epoch_us: float = 100_000.0,  # 100 ms default epoch (§7)
        splitting_c: float = 1.0,
    ):
        self.mmu = mmu
        self.allocator = allocator
        self.epoch_us = epoch_us
        self.splitting = BoundedSplitting(mmu.engine.directory, c=splitting_c)
        self._last_epoch_at_us = 0.0
        self.epoch_reports: list[EpochReport] = []
        # Switchless baseline racks (gam / fastswap) clear this: their
        # models never read the in-network directory, so §4.4 mmap-time
        # pre-population would only burn setup time building entries no
        # lookup will ever touch.
        self.prepopulate_on_mmap = True
        # Multi-switch racks: the VA-range shard map (set by ShardedRack).
        # The shard rebalancer is not copied: it stays off, and no
        # migration latency is ever pending.
        self.rebalance_threshold: float | None = None
        self.rebalance_max_moves = 4
        self.block_accesses: dict[int, int] | None = None
        self.rebalance_reports: list[dict] = []
        self._migration_us_pending = 0.0

    # ------------------------------------------------------------------ #
    # Syscall intercepts (§6.1 'Managing vmas').
    # ------------------------------------------------------------------ #
    def sys_mmap(self, pdid: int, length: int, perm: Perm = Perm.RW,
                 requesting_blade: int | None = None) -> SyscallResult:
        vma = self.allocator.mmap(pdid, length, perm)
        self.mmu.protection.grant_vma(vma)
        if requesting_blade is not None and self.prepopulate_on_mmap:
            # §4.4 pre-population: allocating blade gets exclusive access.
            self.mmu.engine.prepopulate(vma.base, vma.length, requesting_blade)
        return SyscallResult(retval=vma.base, vma=vma)

    # ------------------------------------------------------------------ #
    # Epoch driver (Bounded Splitting, §5).
    # ------------------------------------------------------------------ #
    def maybe_run_epoch(self, now_us: float, split: bool = True) -> EpochReport | None:
        """Fire Bounded Splitting (when ``split``) if the epoch elapsed."""
        if now_us - self._last_epoch_at_us < self.epoch_us:
            return None
        self._last_epoch_at_us = now_us
        report = None
        if split:
            report = self.splitting.run_epoch()
            self.epoch_reports.append(report)
        return report

    def take_migration_charge(self) -> float:
        """Drain the pending migration latency (us).  The engines charge
        it stop-the-world: every thread stalls while region state moves
        between switches over the switch-to-switch links."""
        us, self._migration_us_pending = self._migration_us_pending, 0.0
        return us
