"""The harness end to end on the CPU at a tiny size: a run's result
line, its refusals, and the comparison that decides ``correct`` failing
on the control and on each fault a cell can have.

The tests steer the device check and the compile cache themselves;
``run.py`` has no option for either."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = SPEC["workloads"][0]["name"]


TRAFFIC = sorted(p.stem for p in (ROOT / "benchmarks/chip/traffic")
                 .glob("*.json"))


@pytest.fixture
def tiny(monkeypatch):
    """Every cell at 2 blades x 2 threads and 150 accesses per thread,
    with short epochs so that Bounded Splitting runs, on the CPU.  A
    test may set ``tiny["traffic"]`` to run the cell under another
    traffic mix."""
    import jax

    load = run.load_cell
    state = {"traffic": None}

    def tiny_cell(name):
        spec, cell, config, traffic = load(name)
        if state["traffic"]:
            traffic = json.loads((ROOT / "benchmarks/chip/traffic" /
                                  f"{state['traffic']}.json").read_text())
        config = dict(config, accesses_per_thread=150)
        config["rack"] = dict(config["rack"], num_compute_blades=2,
                              threads_per_blade=2, epoch_us=400.0)
        if traffic["accesses_per_thread"]:
            traffic = dict(traffic, accesses_per_thread=40, pool=3,
                           reference_jobs=2)
        return spec, cell, config, traffic

    monkeypatch.setattr(run, "load_cell", tiny_cell)
    monkeypatch.setattr(run, "require_device",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    return state


def result(capsys, cell, seed=2**31 + 99):
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "0.3", "--trace", "0"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("mix", TRAFFIC)
def test_tiny_cell_runs_end_to_end(tiny, capsys, mix):
    tiny["traffic"] = mix
    out = result(capsys, CELL)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    want = {m["name"] for m in run.metrics_for(SPEC, CELL, False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"] == {k: {"value": out["checks"][k]["value"],
                                 "limit": lim}
                             for k, lim in check.LIMITS.items()}


def _batched(res):
    res.engine = "batched"
    return res


def _plant(monkeypatch, fault):
    """Break the timed path underneath the harness."""
    from repro.core.types import EpochStats
    from repro.dataplane.engine import BatchedDataPlane

    orig = BatchedDataPlane.run

    def broken(self, trace, max_accesses=None):
        if fault == "half_batch":
            return orig(self, trace, len(trace) // 2)
        res = orig(self, trace, max_accesses)
        if fault == "state_unchanged":
            res.stats = EpochStats()
        elif fault == "answer_altered":
            res.stats.invalidations += 1
        elif fault == "time_altered":
            res.runtime_us *= 1 + 1e-7
        return res

    monkeypatch.setattr(BatchedDataPlane, "run", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "time_altered"])
def test_planted_fault_is_not_correct(tiny, capsys, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out = result(capsys, CELL)
    assert out["correct"] is False
    caught = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert caught


def _plant_tcam(monkeypatch, fault):
    """Break one TCAM kernel underneath the engine."""
    from repro.kernels import ops

    if fault == "translate_wrong_row":
        orig = ops.translate_lookup

        def wrong_row(vaddrs, table, **kw):
            blade, rows = orig(vaddrs, table, **kw)
            return blade, (np.asarray(rows) + 1) % len(table)

        monkeypatch.setattr(ops, "translate_lookup", wrong_row)
    elif fault == "translate_wrong_blade":
        orig = ops.translate_lookup

        def wrong_blade(vaddrs, table, **kw):
            blade, rows = orig(vaddrs, table, **kw)
            return np.asarray(blade) + 1, rows

        monkeypatch.setattr(ops, "translate_lookup", wrong_blade)
    elif fault == "protect_denies_writes":
        orig = ops.protect_check

        def denies(pdids, vaddrs, need, table, **kw):
            allow = np.asarray(orig(pdids, vaddrs, need, table, **kw))
            return allow & (np.asarray(need) != 2)

        monkeypatch.setattr(ops, "protect_check", denies)


@pytest.mark.parametrize("fault", ["translate_wrong_row",
                                   "translate_wrong_blade",
                                   "protect_denies_writes"])
def test_planted_tcam_fault_is_not_correct(tiny, capsys, monkeypatch,
                                           fault):
    _plant_tcam(monkeypatch, fault)
    out = result(capsys, CELL)
    assert out["correct"] is False
    assert out["checks"]["tcam_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_control_float32_clocks_is_not_correct(tiny, capsys, monkeypatch,
                                               cell):
    """The reference in the program's place with its modeled clocks in
    float32, the precision below the configurations' float64."""
    monkeypatch.setattr(run, "program_job", lambda job, kw: _batched(
        run.reference_job(job, kw, clock_dtype=np.float32)))
    out = result(capsys, cell)
    assert out["correct"] is False
    assert out["checks"]["time_gap"]["value"] > check.LIMITS["time_gap"]


def test_fault_plane_is_compared(tiny, capsys, monkeypatch):
    tiny["traffic"] = "memblade_fault"
    out = result(capsys, CELL)
    assert out["correct"] is True, out["checks"]
    from repro.dataplane.engine import BatchedDataPlane

    orig = BatchedDataPlane.run

    def lost_pages(self, trace, max_accesses=None):
        res = orig(self, trace, max_accesses)
        res.fault_reports[0].pages_dirty_lost += 1
        return res

    monkeypatch.setattr(BatchedDataPlane, "run", lost_pages)
    out = result(capsys, CELL)
    assert out["correct"] is False
    assert out["checks"]["fault_mismatches"]["value"] > 0


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         CELL, "--seed", "7", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_device_that_is_not_a_tpu(tmp_path):
    p = _run_cli(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
