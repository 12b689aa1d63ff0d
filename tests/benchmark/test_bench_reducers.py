"""The trace reducer and the TCAM work counts, on hand-made inputs."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import peaks, tcam_cost, xplane  # noqa: E402

PLANES = [
    ("/host:CPU", [("python", [("job 0", 0, 100), ("other", 5, 1)])]),
    ("/device:TPU:0", [
        ("XLA Modules", [("jit__translate_call(1)", 10, 10),
                         ("jit__lane_replay(2)", 40, 30),
                         ("jit__lane_replay(2)", 90, 20)]),
        ("XLA Ops", [("fusion.1", 10, 10), ("while.2", 40, 25),
                     ("copy.3", 60, 10), ("while.2", 90, 20)]),
    ]),
    ("/device:TPU:0 SparseCore", [("XLA Ops", [("x", 0, 100)])]),
]


def test_device_planes_and_lines():
    devs = xplane.device_planes(PLANES)
    assert [p[0] for p in devs] == ["/device:TPU:0"]
    ops = xplane.line_events(devs[0], "XLA Ops")
    assert ops[1] == ("while.2", 40, 65)
    assert xplane.host_spans(PLANES, "job ") == [("job 0", 0, 100)]


def test_busy_union_and_gaps():
    ops = xplane.line_events(xplane.device_planes(PLANES)[0],
                             "XLA Ops")
    merged = xplane.merge((s, e) for _, s, e in ops)
    assert merged == [(10, 20), (40, 70), (90, 110)]
    assert xplane.busy_ns(merged, 0, 100) == 10 + 30 + 10
    assert xplane.gaps(merged, 0, 100) == [(0, 10), (20, 40), (70, 90)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]


def test_time_by_name_and_named_seconds():
    ops = xplane.line_events(xplane.device_planes(PLANES)[0],
                             "XLA Ops")
    top = xplane.time_by_name(ops, k=2)
    assert [n for n, _ in top] == ["while.2", "fusion.1"]
    assert [s for _, s in top] == pytest.approx([45e-9, 10e-9])
    mods = xplane.line_events(xplane.device_planes(PLANES)[0],
                              xplane.MODULES_LINE)
    assert xplane.seconds_named(mods, "_lane_replay", 0, 100) == \
        pytest.approx(40e-9)


def test_label_gaps_innermost_span_wins():
    spans = [("job 0", 0, 100), ("job:device", 25, 35),
             ("job:schedule", 20, 40)]
    idle = [(0, 10), (20, 40), (95, 105)]
    got = dict(xplane.label_gaps(idle, spans))
    assert got["job:device"] == pytest.approx(10e-9)
    assert got["job:schedule"] == pytest.approx(10e-9)
    assert got["job 0"] == pytest.approx(15e-9)
    assert got["(no span)"] == pytest.approx(5e-9)


def test_tcam_counts_by_hand():
    # 1000 requests x 7 rows: translate reads 8 B and writes 8 B per
    # request and 16 B per row; protect reads 16 B, writes 1 B per
    # request and reads 20 B per row.
    assert tcam_cost.translate_lookup(1000, 7) == {
        "bytes": 16000 + 112, "compares": 7000}
    assert tcam_cost.protect_check(1000, 7) == {
        "bytes": 17000 + 140, "compares": 7000}


def test_peaks_unknown_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peak("cpu", "hbm_bytes_per_s")


def test_short_name_drops_hlo_text():
    assert xplane.short_name("%while.28 = (s32[]) while(%t), body=%b") == \
        "%while.28"
    assert xplane.short_name("jit__lane_replay(12)") == "jit__lane_replay(12)"


def test_read_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("job 0"):
        jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = xplane.read(xplane.find(str(tmp_path)), "job ")
    spans = xplane.host_spans(planes, "job ")
    assert [n for n, _, _ in spans] == ["job 0"]
    assert all(e > s for _, s, e in spans)
