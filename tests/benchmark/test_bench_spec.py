"""BENCHMARK.json is well formed and every cell finds its files by name."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()
    assert SPEC["command"][1].startswith(SPEC["paths"][0])


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - ENTRY_KEYS[section] - {"workloads"}
        assert not extra and ENTRY_KEYS[section] <= set(e), e["name"]
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "source", "layer"):
            if key in e and section != "end_to_end":
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_bounds_and_moves():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(cell):
    spec, w, config, traffic = run.load_cell(cell)
    assert w["chips"] == 1
    assert config["name"] == w["config"]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    for key in entry["reduced"]:
        assert key in config["reduced"]
    assert (ROOT / "benchmarks/chip/gen" / f"{config['generator']}.py").is_file()
    assert traffic["pool"] >= 1
    for trace in (False, True):
        for m in run.metrics_for(spec, cell, trace):
            assert (ROOT / "benchmarks/chip/metrics"
                    / f"{m['name']}.py").is_file(), m["name"]
    assert run.metrics_for(spec, cell, True)
    assert {"setup_s"} < {m["name"] for m in run.metrics_for(spec, cell,
                                                             False)}
