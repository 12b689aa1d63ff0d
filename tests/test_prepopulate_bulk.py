"""Bulk §4.4 pre-population over fresh VA.

``CoherenceEngine.prepopulate`` hands a vma that lies wholly above its
VA bucket's high-water mark to ``CacheDirectory.bulk_install_fresh``,
which builds only the windows that survive the mapping.  It must leave
exactly the state of the per-window install loop: entries, statistics,
recency order and clock, eviction counters, the pending-eviction queue
and, after the drain, the pre-population marks.  Shard budgets, an
attached telemetry plane, ``eviction="scan"`` and VA that is not fresh
keep the loop, and ``EmulationResult.counters["prepop_bulk_windows"]``
reads 0 there.
"""

import numpy as np
import pytest

from repro.core import traces as T
from repro.core.cache import BladePageCache
from repro.core.coherence import CoherenceEngine
from repro.core.directory import CacheDirectory
from repro.core.emulator import DisaggregatedRack, ShardedRack
from repro.core.types import (
    PAGE_SIZE,
    AccessType,
    MemAccess,
    SwitchResources,
)
from repro.telemetry import Telemetry

BASE = 1 << 40
WIN = 1 << 14  # the initial region size
NBLADES = 4


def _engine(capacity):
    d = CacheDirectory(initial_region_log2=14,
                       resources=SwitchResources(
                           max_directory_entries=capacity))
    caches = {b: BladePageCache(b, 64 * PAGE_SIZE) for b in range(NBLADES)}
    return CoherenceEngine(d, caches)


def _play(eng, ops, per_window_only=False):
    """Apply ``ops`` to ``eng``; with ``per_window_only`` every
    pre-population takes the per-window loop."""
    d = eng.directory
    if per_window_only:
        d.can_bulk_install = lambda bases: False
    for op in ops:
        kind = op[0]
        if kind == "install":  # a directory miss left Invalid
            d.get_or_create(BASE + op[1] * WIN)
        elif kind == "access":  # S or M, and stale maybe-Invalid keys
            _, blade, win, write = op
            eng.access(MemAccess(blade, 1, BASE + win * WIN + PAGE_SIZE,
                                 AccessType.WRITE if write
                                 else AccessType.READ))
        elif kind == "lookup":
            d.lookup(BASE + op[1] * WIN)
        elif kind == "mmap":  # a new vma past everything mapped so far
            _, nwin, owner, skew = op
            top = max(d.va_high.values(), default=BASE)
            eng.prepopulate(top + skew, nwin * WIN - skew, owner)
        elif kind == "remap":  # pre-populate VA that is already mapped
            _, first, nwin, owner = op
            eng.prepopulate(BASE + first * WIN, nwin * WIN, owner)
        elif kind == "drain":
            eng._drain_capacity_evictions()
    return eng


def _fields(e):
    return (e.base, e.size_log2, e.state, e.sharers, e.owner)


def _assert_same(a, b):
    da, db = a.directory, b.directory
    assert list(da.entries) == list(db.entries)
    assert [_fields(e) for e in da.entries.values()] == \
        [_fields(e) for e in db.entries.values()]
    assert list(da.stats.items()) == list(db.stats.items())  # last_touch
    assert list(da._lru) == list(db._lru)
    assert list(da._ilru) == list(db._ilru)
    assert da._clock == db._clock
    assert da.capacity_evictions == db.capacity_evictions
    assert da.peak_entries == db.peak_entries
    assert da.va_high == db.va_high
    assert [_fields(e) for e in da.pending_evictions] == \
        [_fields(e) for e in db.pending_evictions]
    a._drain_capacity_evictions()
    b._drain_capacity_evictions()
    assert a._prepopulated == b._prepopulated
    assert a.stats == b.stats
    for blade in range(NBLADES):
        assert list(a.caches[blade].pages.items()) == \
            list(b.caches[blade].pages.items())


def _both(capacity, ops):
    bulk = _play(_engine(capacity), ops)
    loop = _play(_engine(capacity), ops, per_window_only=True)
    _assert_same(bulk, loop)
    return bulk


#: Pre-existing entries in I, S and M (with stale maybe-Invalid keys),
#: touched out of install order.
HISTORY = [("install", 0), ("access", 1, 1, False), ("install", 2),
           ("access", 2, 3, True), ("access", 0, 4, False),
           ("install", 5), ("access", 3, 0, True), ("lookup", 2),
           ("access", 1, 6, False), ("install", 7), ("lookup", 5)]


@pytest.mark.parametrize("capacity,nwin", [
    (6, 1), (6, 20), (8, 8), (12, 4), (19, 8), (20, 3), (20, 13),
    (20, 60), (500, 60), (1, 7)])
@pytest.mark.parametrize("skew", [0, PAGE_SIZE])
def test_bulk_equals_loop_after_history(capacity, nwin, skew):
    """Capacity below, at and far above the windows, from a directory
    holding I, S and M entries."""
    ops = HISTORY + [("mmap", nwin, 2, skew)]
    eng = _both(capacity, ops)
    assert eng.prepop_bulk_windows == nwin


@pytest.mark.parametrize("capacity", [5, 16, 40, 1000])
def test_bulk_equals_loop_over_successive_mmaps(capacity):
    ops = HISTORY + [("mmap", 9, 0, 0), ("mmap", 30, 1, 0),
                     ("access", 2, 8, True), ("mmap", 4, 3, 0),
                     ("drain",), ("mmap", 11, 2, PAGE_SIZE)]
    eng = _both(capacity, ops)
    assert eng.prepop_bulk_windows == 9 + 30 + 4 + 11


def test_bulk_matches_loop_on_the_benchmark_shape():
    """One 2 GB vma of 16 KB windows into 30k slots: the last 30k
    survive in M at the owner, the first 101,072 are queued in order."""
    bulk = _play(_engine(30_000), [("mmap", 131_072, 0, 0)])
    loop = _play(_engine(30_000), [("mmap", 131_072, 0, 0)],
                 per_window_only=True)
    d = bulk.directory
    assert len(d.entries) == 30_000 and d.capacity_evictions == 101_072
    assert d.pending_evictions[0].base == BASE
    assert next(iter(d._lru)) == (BASE + 101_072 * WIN, 14)
    _assert_same(bulk, loop)


try:  # property tests need hypothesis (CI dev extra); the rest run bare
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    pass
else:
    _op = st.one_of(
        st.tuples(st.just("install"), st.integers(0, 12)),
        st.tuples(st.just("access"), st.integers(0, NBLADES - 1),
                  st.integers(0, 12), st.booleans()),
        st.tuples(st.just("lookup"), st.integers(0, 12)),
        st.tuples(st.just("mmap"), st.integers(0, 40),
                  st.integers(0, NBLADES - 1),
                  st.sampled_from([0, PAGE_SIZE, WIN - PAGE_SIZE])),
        st.tuples(st.just("remap"), st.integers(0, 12), st.integers(1, 6),
                  st.integers(0, NBLADES - 1)),
        st.tuples(st.just("drain")))

    @settings(max_examples=150, deadline=None)
    @given(st.integers(1, 48), st.lists(_op, max_size=25))
    def test_bulk_equals_loop_hypothesis(capacity, ops):
        _both(capacity, ops)


# --------------------------------------------------------------------- #
# Fall-backs: the per-window loop runs and the counter reads 0.
# --------------------------------------------------------------------- #
@pytest.fixture
def bulk_calls(monkeypatch):
    calls = []
    bulk = CacheDirectory.bulk_install_fresh

    def spy(self, bases, *args):
        calls.append(len(bases))
        return bulk(self, bases, *args)

    monkeypatch.setattr(CacheDirectory, "bulk_install_fresh", spy)
    return calls


def test_non_fresh_va_takes_the_loop(bulk_calls):
    eng = _play(_engine(20), HISTORY + [("remap", 0, 9, 1)])
    assert bulk_calls == [] and eng.prepop_bulk_windows == 0
    # A vma starting below the mark but running past it is not fresh.
    top = max(eng.directory.va_high.values())
    eng.prepopulate(top - WIN, 4 * WIN, 2)
    assert bulk_calls == [] and eng.prepop_bulk_windows == 0


def _ycsb(store_mb=8):
    return T.ycsb_trace("zipf", num_threads=4, read_ratio=0.5,
                        accesses_per_thread=150, store_mb=store_mb, seed=3)


_RACK = dict(system="mind", num_compute_blades=2, threads_per_blade=2,
             max_directory_entries=120, splitting_enabled=False)


def test_batched_counts_bulk_windows(bulk_calls):
    res = DisaggregatedRack(engine="batched", **_RACK).run(_ycsb())
    assert res.counters["prepop_bulk_windows"] == (8 << 20) // WIN
    assert sum(bulk_calls) == (8 << 20) // WIN


@pytest.mark.parametrize("fallback", ["shard_budgets", "telemetry", "scan"])
def test_fallbacks_take_the_loop(fallback, bulk_calls):
    trace = _ycsb()
    if fallback == "shard_budgets":
        kw = {k: v for k, v in _RACK.items()
              if k != "max_directory_entries"}
        rack = ShardedRack(num_shards=2, shard_slot_budgets=60,
                           engine="batched", **kw)
    elif fallback == "telemetry":
        rack = DisaggregatedRack(engine="batched", telemetry=Telemetry(),
                                 **_RACK)
    else:
        rack = DisaggregatedRack(engine="batched",
                                 directory_eviction="scan", **_RACK)
    res = rack.run(trace)
    assert res.counters["prepop_bulk_windows"] == 0
    assert bulk_calls == []
    assert rack.mmu.engine.directory.capacity_evictions > 0


def test_overflowing_prepopulation_parity(monkeypatch):
    """Pre-population overflows the directory more than 3x: the batched
    engine, the scalar engine and the scalar engine held to the
    per-window loop agree on every statistic, epoch and the runtime."""
    trace = _ycsb(store_mb=8)
    kw = dict(_RACK, splitting_enabled=True, epoch_us=4000.0)
    assert (8 << 20) // WIN > 3 * kw["max_directory_entries"]
    rb = DisaggregatedRack(engine="batched", **kw).run(trace)
    rs = DisaggregatedRack(engine="scalar", **kw).run(trace)
    monkeypatch.setattr(CacheDirectory, "can_bulk_install",
                        lambda self, bases: False)
    rl = DisaggregatedRack(engine="scalar", **kw).run(trace)
    assert rb.counters["prepop_bulk_windows"] == (8 << 20) // WIN
    assert rb.stats.accesses == len(trace)
    assert rs.latency_breakdown_us == rl.latency_breakdown_us
    for other in (rs, rl):
        assert rb.stats == other.stats
        assert rb.epoch_reports == other.epoch_reports
        assert rb.directory_timeline == other.directory_timeline
        assert rb.runtime_us == other.runtime_us
        assert rb.total_thread_us == other.total_thread_us
        # The batched engine sums each component in another order.
        np.testing.assert_allclose(
            list(rb.latency_breakdown_us.values()),
            list(other.latency_breakdown_us.values()), rtol=1e-12)
