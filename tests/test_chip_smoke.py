"""chip_smoke.py off the chip: it refuses anything but a TPU, refuses to
run without the repository's sources, and its phases (replay, then
scalar-vs-batched prefix parity) pass at a tiny size with interpreted
kernels.  Also where the compile cache lands."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.core import traces

ROOT = Path(__file__).resolve().parents[1]


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_cpu():
    p = _run(ROOT / "chip_smoke.py", ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_refuses_without_sources(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    p = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert p.returncode != 0
    assert "no repro sources" in p.stderr
    assert '"ok"' not in p.stdout


def test_phases_pass_at_tiny_size(monkeypatch, capsys):
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke

    # Interpreted kernels carry no Mosaic call; everything else is real.
    monkeypatch.setattr(chip_smoke, "check_mosaic", lambda batch: None)
    log = chip_smoke.CompileLog()
    chip_smoke.run_phase(
        "A", dict(num_compute_blades=8, threads_per_blade=10,
                  epoch_us=100_000.0),
        traces.ma_trace(80, accesses_per_thread=100), 4_000, log)
    chip_smoke.run_phase(
        "B", dict(num_compute_blades=8, threads_per_blade=4),
        traces.tf_trace(32, accesses_per_thread=100), 2_000, log)
    out = capsys.readouterr().out
    assert out.count("prefix parity held") == 2


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_env_stays_in_charge(monkeypatch, tmp_path,
                                           restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
