"""Host-time spans and work counters of the batched engine.

A replay under ``jax.profiler`` leaves one ``mind.run`` span per replay
with one ``mind.<phase>`` child per ``PHASES`` phase (their durations
add up to ``phase_times``), spans for the steps between phases, and a
``mind.speculate`` span per speculative attempt, a discarded one holding
a ``mind.spec_rollback``.  ``EmulationResult.counters`` counts the
waves, lane slots, packets and host-device bytes of the device calls.
The spans are read with the chip benchmark's own trace reader.
"""

import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import traces as T
from repro.core.emulator import DisaggregatedRack
from repro.dataplane import engine as E
from repro.kernels import ops
from repro.telemetry import Telemetry
from repro.telemetry import events as tev

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import xplane  # noqa: E402

#: A phase's spans add up to its ``phase_times`` entry within 2%, and
#: a millisecond per span: the profiler stamps a span as it enters and
#: leaves it, just outside the phase's own clock readings, and a process
#: descheduled in between (the suite runs in parallel) lengthens a span.
TOL, SLACK_S = 0.02, 1e-3


def _trace(n=600):
    return T.ycsb_trace("zipf", num_threads=4, read_ratio=0.5,
                        accesses_per_thread=n, store_mb=4, seed=7)


def _rack(**kw):
    kw.setdefault("splitting_enabled", False)
    return DisaggregatedRack(system="mind", engine="batched",
                             num_compute_blades=2, threads_per_blade=2,
                             max_directory_entries=120, **kw)


def _spec_rack(**kw):
    """Epochs on and small chunks: speculative chunks, some discarded."""
    return _rack(splitting_enabled=True, epoch_us=4000.0,
                 engine_options={"chunk_size": 97}, **kw)


def _recorded(tmp_path, make_rack, trace):
    """The replay's result and its ``mind.*`` spans, (name, start, end)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = make_rack().run(trace)
    finally:
        jax.profiler.stop_trace()
    planes = xplane.read(xplane.find(str(tmp_path)), "mind.")
    return res, sorted(xplane.host_spans(planes, "mind."),
                       key=lambda sp: sp[1])


def _inside(sp, outer):
    return outer[1] <= sp[1] and sp[2] <= outer[2]


def _seconds(spans):
    return sum(e - s for _, s, e in spans) * 1e-9


def _adds_up(spans, secs):
    return abs(_seconds(spans) - secs) <= TOL * secs + SLACK_S * len(spans)


def _covers(spans, secs):
    """The spans hold at least the phase's time: none is missing."""
    return _seconds(spans) >= (1 - TOL) * secs


def _named(spans, name):
    return [sp for sp in spans if sp[0] == f"mind.{name}"]


def _discarded(spans):
    """The ``mind.speculate`` spans that hold a ``mind.spec_rollback``."""
    rb = _named(spans, "spec_rollback")
    return [sp for sp in _named(spans, "speculate")
            if any(_inside(r, sp) for r in rb)]


def _deferral_checks(spans):
    """The residency spans of fast-path attempts that could not defer:
    in a ``mind.speculate`` that falls back to a snapshot, the ones
    before ``mind.snapshot``.  No phase counts them."""
    out = []
    for sp in _named(spans, "speculate"):
        snaps = [s for s in _named(spans, "snapshot") if _inside(s, sp)]
        out += [r for r in _named(spans, "residency_prepass")
                if snaps and _inside(r, sp) and r[2] <= snaps[0][1]]
    return out


def _covered_share(spans, outer):
    """Share of ``outer``'s time that the spans inside it cover."""
    inner = [(s, e) for n, s, e in spans
             if (n, s, e) != outer and _inside((n, s, e), outer)]
    return xplane.busy_ns(xplane.merge(inner), outer[1], outer[2]) \
        / (outer[2] - outer[1])


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    """A replay with the epoch driver off, under directory pressure."""
    return _recorded(tmp_path_factory.mktemp("plain"), _rack, _trace())


@pytest.fixture(scope="module")
def speculative(tmp_path_factory):
    """A replay whose speculative epoch chunks are partly discarded."""
    tel = Telemetry()
    res, spans = _recorded(tmp_path_factory.mktemp("spec"),
                           lambda: _spec_rack(telemetry=tel), _trace())
    return res, spans, tel


def test_phases_nest_in_run_and_add_up(plain):
    res, spans = plain
    runs = _named(spans, "run")
    assert len(runs) == 1
    for key, secs in res.phase_times.items():
        if secs == 0.0:
            continue
        mine = _named(spans, key)
        assert mine and all(_inside(sp, runs[0]) for sp in mine), key
        assert _adds_up(mine, secs), key
    assert res.phase_times["speculation_overhead"] == 0.0
    assert not _named(spans, "speculate")


def test_arena_and_rack_spans(plain):
    _, spans = plain
    arena = _named(spans, "arena_setup")
    assert len(arena) == 1
    for child in ("arena_alloc", "arena_prepopulate"):
        mine = _named(spans, child)
        assert mine and all(_inside(sp, arena[0]) for sp in mine), child
    # The rack's construction and the engine's: outside the replay.
    builds = _named(spans, "rack_build")
    assert len(builds) == 2
    assert all(sp[2] <= _named(spans, "run")[0][1] for sp in builds)


def test_children_cover_the_run(plain):
    """The steps of a replay that are no phase have spans too: inside
    ``mind.run`` little host time is left outside every child span."""
    _, spans = plain
    assert _covered_share(spans, _named(spans, "run")[0]) > 0.95


def test_discarded_speculation_tells_from_committed(speculative):
    res, spans, tel = speculative
    spec = _named(spans, "speculate")
    gone = _discarded(spans)
    rollbacks = tel.recorder.counts_by_kind().get(tev.SPEC_ROLLBACK, 0)
    assert rollbacks > 0
    assert len(gone) == rollbacks
    assert len(spec) > len(gone)  # some attempts commit
    # Phases of kept work cover phase_times; the discarded attempts and
    # the snapshots of the kept ones cover the speculation overhead.
    checks = _deferral_checks(spans)
    assert checks  # the directory is under pressure: no chunk defers
    kept = [sp for sp in spans if sp not in checks
            and not any(_inside(sp, g) for g in gone)]
    for key, secs in res.phase_times.items():
        if key == "speculation_overhead" or secs == 0.0:
            continue
        assert _covers(_named(kept, key), secs), key
    assert _covers(gone + _named(kept, "snapshot"),
                   res.phase_times["speculation_overhead"])


def _spied(monkeypatch, make_rack, trace):
    """Replay with every device call of the engine and every directory
    eviction its residency pre-pass injects counted by hand."""
    seen = dict(waves=0, wave_slots=0, packets=0, h2d_bytes=0,
                d2h_bytes=0, evictions=0)

    def ship(args, outs):
        seen["h2d_bytes"] += sum(np.asarray(a).nbytes for a in args)
        seen["d2h_bytes"] += sum(np.asarray(o).nbytes for o in outs)

    replay, protect, translate = E._replay, ops.protect_check, \
        ops.translate_lookup

    def spy_replay(*args):
        outs = replay(*args)
        ship(args, outs)
        waves = int(args[0])
        seen["waves"] += waves
        seen["wave_slots"] += args[2].shape[0] * waves
        seen["packets"] += int(np.asarray(args[5]).sum())
        return outs

    def spy_protect(*args, **kw):
        allow = protect(*args, **kw)
        ship(args, (allow,))
        return allow

    def spy_translate(*args, **kw):
        outs = translate(*args, **kw)
        ship(args, outs)
        return outs

    prepass = E.BatchedDataPlane._residency_prepass

    def spy_prepass(self, *args):
        out = prepass(self, *args)
        seen["evictions"] += len(out[2])
        return out

    monkeypatch.setattr(E, "_replay", spy_replay)
    monkeypatch.setattr(ops, "protect_check", spy_protect)
    monkeypatch.setattr(ops, "translate_lookup", spy_translate)
    monkeypatch.setattr(E.BatchedDataPlane, "_residency_prepass",
                        spy_prepass)
    return make_rack().run(trace), seen


def test_counters_by_hand(monkeypatch):
    res, seen = _spied(monkeypatch, _rack, _trace())
    c = res.counters
    assert set(c) == set(E.COUNTERS)
    assert seen["evictions"] > 0  # directory pressure injected packets
    assert c["packets"] == res.stats.accesses + seen["evictions"]
    assert c["wave_slots"] >= c["packets"] > 0
    for k in E.COUNTERS:
        assert c[k] == seen[k], k
    # Each call ships at least one copy of the plane bitmaps per lane.
    assert c["h2d_bytes"] > c["packets"] * 4


def test_counters_count_discarded_attempts(monkeypatch):
    """A discarded speculative chunk's device work still counts."""
    res, seen = _spied(monkeypatch, _spec_rack, _trace())
    for k in E.COUNTERS:
        assert res.counters[k] == seen[k], k
    assert res.counters["packets"] > res.stats.accesses + seen["evictions"]


def test_profiler_changes_no_result(speculative):
    traced, _, _ = speculative
    res = _spec_rack(telemetry=Telemetry()).run(_trace())
    assert res.stats == traced.stats
    assert res.epoch_reports == traced.epoch_reports
    assert res.directory_timeline == traced.directory_timeline
    assert res.runtime_us == traced.runtime_us
    assert res.total_thread_us == traced.total_thread_us
    assert res.latency_breakdown_us == traced.latency_breakdown_us
    assert res.counters == traced.counters
    assert set(res.phase_times) == set(E.PHASES)


def test_scalar_engine_loads_no_jax_and_counts_nothing():
    code = ("import sys\n"
            "from repro.core.emulator import run_workload\n"
            "r = run_workload('mind', 'GC', num_compute_blades=2,\n"
            "                 threads_per_blade=2, accesses_per_thread=50)\n"
            "assert r.engine == 'scalar' and r.counters == {}\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": str(ROOT / "src"), "PATH": ""})
