"""Balanced memory allocation (§4.1).

The control plane tracks total allocation per memory blade and places each
new vma on the *least-allocated* blade (near-optimal load balancing,
validated in Fig. 9 right via Jain's fairness index).  Inside a blade the
bytes are carved address-ordered first fit, the program's default fit
policy (its other policies are not copied: no configuration selects one).

Allocations are rounded up to power-of-two sizes and aligned to their size
(§4.4) so each vma's protection needs a *single* TCAM entry.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmarks.chip.reference.address_space import GlobalAddressSpace
from benchmarks.chip.reference.types import PAGE_SIZE, VMA, Perm, align_up, next_pow2


@dataclass
class FreeBlock:
    """One free extent in an address-ordered free list."""

    base: int
    length: int

    @property
    def end(self) -> int:
        return self.base + self.length


class BladeAllocator:
    """One blade's VA range [va_base, va_base+capacity), carved
    address-ordered first fit over a sorted, coalesced free list."""

    def __init__(self, va_base: int, capacity: int):
        self.va_base = va_base
        self.capacity = capacity
        self.allocated = 0
        self.free: list[FreeBlock] = [FreeBlock(va_base, capacity)]

    def alloc(self, length: int, align: int) -> int | None:
        """The lowest base with room at ``align``; None if no room."""
        for i, blk in enumerate(self.free):
            base = align_up(blk.base, align)
            if base + length <= blk.end:
                tail = FreeBlock(base + length, blk.end - (base + length))
                head = FreeBlock(blk.base, base - blk.base)
                self.free[i:i + 1] = [b for b in (head, tail) if b.length > 0]
                self.allocated += length
                return base
        return None


class MemoryAllocator:
    """Control-plane allocator: balanced placement + per-blade first fit."""

    def __init__(self, gas: GlobalAddressSpace, pow2_align: bool = True):
        self.gas = gas
        self.pow2_align = pow2_align
        self.blades: dict[int, BladeAllocator] = {}
        self.vmas: dict[int, VMA] = {}  # keyed by base address
        # Quarantined (failed) blades: excluded from placement until a
        # blade_restore fault revives them (repro.core.faults).
        self.dead: set[int] = set()
        for b, spec in gas.blades.items():
            self.blades[b] = BladeAllocator(spec.va_base, spec.capacity)

    def _rounded(self, length: int) -> tuple[int, int]:
        """(rounded_length, alignment).  pow2 rounding per §4.4 so the vma
        fits one TCAM entry; callers can disable to measure the trade-off
        (benchmarks/fig9_resources.py does)."""
        length = align_up(length, PAGE_SIZE)
        if self.pow2_align:
            length = next_pow2(length)
            return length, length
        return length, PAGE_SIZE

    def mmap(self, pdid: int, length: int, perm: Perm = Perm.RW) -> VMA:
        """Allocate a vma; places on least-allocated blade (§4.1)."""
        if length <= 0:
            # align_up(0) == 0 and next_pow2(0) == 1 used to mint a
            # 1-byte, non-page vma here — reject instead.
            raise ValueError(
                f"mmap length must be positive, got {length}")
        rlen, align = self._rounded(length)
        # Least-allocated first; fall back across blades if fragmented.
        # Quarantined blades never receive placements.
        order = sorted((b for b in self.blades if b not in self.dead),
                       key=lambda b: (self.blades[b].allocated, b))
        for blade_id in order:
            base = self.blades[blade_id].alloc(rlen, align)
            if base is not None:
                vma = VMA(base=base, length=rlen, pdid=pdid, perm=perm, blade_id=blade_id)
                self.vmas[base] = vma
                return vma
        raise MemoryError(f"out of disaggregated memory for request of {length} bytes")
