"""In-network cache directory with variable-granularity regions (§4.3, §6.3).

The directory maps a *region* (pow2-sized, naturally aligned, 4 KB..M) to
its MSI state and sharer bitmap.  Entries live in a fixed pool of SRAM
slots on the switch; the control plane owns a free list and installs a
match-action rule per entry (modelled by the (base, log2) keyed map here
and materialized for the data-plane kernel via ``export_tables``).

Invariants this module maintains (and the rest of the stack relies on):

* **Buddy alignment** — every region is a power-of-two sized,
  naturally-aligned interval (``base % size == 0``) no larger than
  ``1 << max_region_log2`` (M) and no smaller than a page.  Region
  boundaries form a buddy system inside each M-sized partition of the VA
  space, so ``lookup`` probes at most ``log2(M) - 12 + 1`` aligned bases
  — this mirrors the staged TCAM lookup and keeps the Python control
  plane fast.  ``split``/``merge`` only ever move one buddy level at a
  time, so the buddy structure is preserved by construction.
* **Most-specific-wins lookup** — after capacity evictions punch holes
  that ``get_or_create`` later re-covers at the initial granularity,
  regions may *overlap* (a coarse re-install over surviving split
  children).  ``lookup`` probes small levels first, so the smallest
  (most specific) region containing an address always wins — the LPM
  order ``export_tables`` materializes for the data plane.
* **Eviction order** — capacity eviction drops the coldest Invalid
  entry if one exists, else the coldest entry overall, where "coldest"
  means least-recently installed-or-looked-up.  The order is tracked by
  two intrusive recency lists (`OrderedDict`s), giving amortized-O(1)
  eviction instead of the seed's O(n) scan; ``eviction="scan"``
  preserves the seed implementation as a reference oracle for tests and
  benchmarks, and the two are property-tested to pick identical victims
  (tests/test_directory_coherence.py).
* **Monotone states** — an entry's MSI state never returns to Invalid
  under the same (base, log2) key: I -> {S, M} on first use, then only
  S <-> M.  Re-installation after an eviction creates a *fresh* entry.
  The lazy maybe-Invalid recency list exploits this: once an entry is
  observed non-Invalid it is pruned and never reconsidered, which is
  what keeps eviction amortized O(1).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

from benchmarks.chip.reference.types import (
    PAGE_SHIFT,
    DirectoryEntry,
    MSIState,
    SwitchResources,
    align_down,
)

DEFAULT_MAX_REGION_LOG2 = 21  # M = 2 MB (512 pages), as in the paper's Fig. 10
DEFAULT_INITIAL_REGION_LOG2 = 14  # 16 KB default initial region (§5, §7)


@dataclass(slots=True)
class RegionStats:
    """Per-entry counters for the current epoch (feeds Bounded Splitting)."""

    false_invalidations: int = 0
    accesses: int = 0
    last_touch: int = 0  # logical time, for capacity-pressure eviction


class CacheDirectory:
    """Control-plane + data-plane view of the region directory."""

    VA_BUCKET_LOG2 = 36  # = the default 64 GB per-blade VA span

    def __init__(
        self,
        max_region_log2: int = DEFAULT_MAX_REGION_LOG2,
        initial_region_log2: int = DEFAULT_INITIAL_REGION_LOG2,
        resources: SwitchResources | None = None,
        eviction: str = "lru",
    ):
        assert PAGE_SHIFT <= initial_region_log2 <= max_region_log2
        assert eviction in ("lru", "scan")
        self.max_region_log2 = max_region_log2
        self.initial_region_log2 = initial_region_log2
        self.resources = resources or SwitchResources()
        self.eviction = eviction
        self.entries: dict[tuple[int, int], DirectoryEntry] = {}
        self.stats: dict[tuple[int, int], RegionStats] = {}
        self._clock = 0
        # Intrusive recency lists (coldest first).  ``_lru`` holds every
        # entry; ``_ilru`` holds the entries that were installed Invalid
        # and have not yet been *observed* to leave I (lazy pruning —
        # states are monotone away from I, so a pruned key never needs
        # to come back).
        self._lru: "OrderedDict[tuple[int, int], None]" = OrderedDict()
        self._ilru: "OrderedDict[tuple[int, int], None]" = OrderedDict()
        # Per-bucket high-water marks of installed region ends: an
        # address at or beyond its bucket's mark provably misses at
        # every level (regions are pow2-sized, naturally aligned and
        # <= 2**max_region_log2 <= the bucket size, so none crosses a
        # bucket boundary), which lets bulk installs over fresh vmas
        # (prepopulation) skip the per-window lookup probe.  Buckets
        # match the per-blade VA spans of the global address space.
        assert max_region_log2 <= self.VA_BUCKET_LOG2
        self.va_high: dict[int, int] = {}
        # Telemetry for Fig. 9 (left) and §7.2.
        self.peak_entries = 0
        self.capacity_evictions = 0
        # Entries force-evicted under capacity pressure that still had
        # sharers; the coherence engine drains this and multicasts
        # invalidations.
        self.pending_evictions: list[DirectoryEntry] = []

    # ------------------------------------------------------------------ #
    # Recency maintenance.
    # ------------------------------------------------------------------ #
    def touch_key(self, key: tuple[int, int]) -> None:
        """Mark ``key`` most-recently-used (the data-plane lookup hit)."""
        self._clock += 1
        self.stats[key].last_touch = self._clock
        self._lru.move_to_end(key)
        if key in self._ilru:
            self._ilru.move_to_end(key)

    def _unlink(self, key: tuple[int, int]) -> None:
        self._lru.pop(key, None)
        self._ilru.pop(key, None)

    # ------------------------------------------------------------------ #
    # Lookup.
    # ------------------------------------------------------------------ #
    def lookup(self, vaddr: int) -> DirectoryEntry | None:
        """Find the most-specific region entry containing vaddr, if any."""
        for log2 in range(PAGE_SHIFT, self.max_region_log2 + 1):
            key = (align_down(vaddr, 1 << log2), log2)
            e = self.entries.get(key)
            if e is not None:
                self.touch_key(key)
                return e
        return None

    def get_or_create(self, vaddr: int) -> DirectoryEntry:
        """Directory-miss path (§6.3): allocate a slot from the free list and
        create the region covering vaddr at the initial granularity."""
        e = self.lookup(vaddr)
        if e is not None:
            return e
        log2 = self.initial_region_log2
        base = align_down(vaddr, 1 << log2)
        return self._install(base, log2)

    def _install(self, base: int, log2: int, state: MSIState = MSIState.I,
                 sharers: int = 0, owner: int = -1) -> DirectoryEntry:
        key = (base, log2)
        if len(self.entries) >= self.resources.max_directory_entries:
            self.evict_for_capacity()
        e = DirectoryEntry(base=base, size_log2=log2, state=state,
                           sharers=sharers, owner=owner)
        self.entries[key] = e
        end = base + (1 << log2)
        bucket = base >> self.VA_BUCKET_LOG2
        if end > self.va_high.get(bucket, 0):
            self.va_high[bucket] = end
        self._clock += 1
        self.stats[key] = RegionStats(last_touch=self._clock)
        self._lru[key] = None
        if state == MSIState.I:
            self._ilru[key] = None
        self.peak_entries = max(self.peak_entries, len(self.entries))
        return e

    # ------------------------------------------------------------------ #
    # Capacity eviction (amortized O(1)).
    # ------------------------------------------------------------------ #
    def pick_victim(self, state_of=None) -> tuple[int, int]:
        """Choose the eviction victim: coldest Invalid entry, else the
        coldest entry overall.

        ``state_of`` optionally overrides how a key's current MSI state
        is read — the batched data plane passes a shadow view because
        its device write-back lags the host walk.  Keys observed to have
        left Invalid are pruned from the maybe-Invalid list (states are
        monotone away from I, see the module docstring), which is what
        makes the amortized cost O(1).
        """
        if self.eviction == "scan":
            keys = list(self.entries)
            get_state = state_of or (lambda k: self.entries[k].state)
            inval = [k for k in keys if get_state(k) == MSIState.I]
            pool = inval if inval else keys
            return min(pool, key=lambda k: self.stats[k].last_touch)
        ilru, lru = self._ilru, self._lru
        get_state = state_of or (lambda k: self.entries[k].state)
        while ilru:
            k = next(iter(ilru))
            if get_state(k) == MSIState.I:
                return k
            del ilru[k]  # left I; it can never return under this key
        return next(iter(lru))

    def evict_for_capacity(self, state_of=None,
                           queue_pending: bool = True) -> DirectoryEntry:
        """SRAM slots exhausted: drop the coldest Invalid entry, else the
        coldest entry overall.  When ``queue_pending`` the
        victim (if it still had sharers) is surfaced via
        ``pending_evictions`` so the coherence engine multicasts
        invalidations — the §7.2 'directory storage becomes the
        bottleneck' behaviour; the batched engine passes
        ``queue_pending=False`` and drains the invalidation as an
        in-stream eviction packet instead."""
        victim = self.pick_victim(state_of)
        e = self.entries.pop(victim)
        self.stats.pop(victim)
        self._unlink(victim)
        self.capacity_evictions += 1
        if queue_pending and e.state != MSIState.I:
            self.pending_evictions.append(e)
        return e

    # ------------------------------------------------------------------ #
    # Split / merge primitives used by Bounded Splitting (§5).
    # ------------------------------------------------------------------ #
    def split(self, entry: DirectoryEntry) -> tuple[DirectoryEntry, DirectoryEntry]:
        """Split a region into two buddies inheriting coherence state.

        Inheriting (state, sharers, owner) is conservative and safe: a
        child can only be *over*-approximate about sharers, never under.
        """
        assert entry.size_log2 > PAGE_SHIFT, "cannot split a 4 KB region"
        key = (entry.base, entry.size_log2)
        assert key in self.entries
        del self.entries[key]
        self.stats.pop(key)
        self._unlink(key)
        child_log2 = entry.size_log2 - 1
        left = self._install(entry.base, child_log2, entry.state, entry.sharers, entry.owner)
        right = self._install(
            entry.base + (1 << child_log2), child_log2, entry.state, entry.sharers, entry.owner
        )
        return left, right

    def merge(self, left: DirectoryEntry, right: DirectoryEntry) -> DirectoryEntry:
        """Merge two buddies (must be coherence-compatible)."""
        assert left.size_log2 == right.size_log2
        assert left.base ^ (1 << left.size_log2) == right.base
        lo = min(left.base, right.base)
        assert lo % (1 << (left.size_log2 + 1)) == 0
        merged_state, sharers, owner = self._merged_coherence(left, right)
        for e in (left, right):
            key = (e.base, e.size_log2)
            del self.entries[key]
            self.stats.pop(key)
            self._unlink(key)
        return self._install(lo, left.size_log2 + 1, merged_state, sharers, owner)

    @staticmethod
    def _merged_coherence(left: DirectoryEntry, right: DirectoryEntry):
        states = (left.state, right.state)
        if MSIState.M in states:
            owner = left.owner if left.state == MSIState.M else right.owner
            return MSIState.M, 0, owner
        if MSIState.S in states:
            return MSIState.S, left.sharers | right.sharers, -1
        return MSIState.I, 0, -1

    # ------------------------------------------------------------------ #
    # Epoch bookkeeping.
    # ------------------------------------------------------------------ #
    def record_false_invalidations(self, entry: DirectoryEntry, count: int) -> None:
        key = (entry.base, entry.size_log2)
        if key in self.stats:
            self.stats[key].false_invalidations += count

    def record_access(self, entry: DirectoryEntry) -> None:
        key = (entry.base, entry.size_log2)
        if key in self.stats:
            self.stats[key].accesses += 1

    def reset_epoch_counters(self) -> None:
        for s in self.stats.values():
            s.false_invalidations = 0
            s.accesses = 0

    # ------------------------------------------------------------------ #
    def num_entries(self) -> int:
        return len(self.entries)

    def utilization(self) -> float:
        return len(self.entries) / self.resources.max_directory_entries

    def entries_in(self, base: int, length: int) -> list[DirectoryEntry]:
        return [
            e
            for e in self.entries.values()
            if e.base < base + length and base < e.end
        ]
