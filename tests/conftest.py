"""Test fixtures.

The suite runs on the CPU (``JAX_PLATFORMS=cpu``), where
``repro.kernels.ops`` runs the Pallas kernels in interpret mode; the TPU
compiler is exercised only by the ahead-of-time compiles of
``test_tpu_compile.py``, and on a chip by ``chip_smoke.py``.  No
persistent compile cache is enabled here.

NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see
the single real CPU device; only launch/dryrun.py forces 512
placeholder devices (and it does so before importing jax)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
