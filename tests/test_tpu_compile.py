"""Ahead-of-time compiles of the replay engine's device programs for a
described TPU v5e chip (no chip attached): the two Pallas TCAM kernels
must lower to Mosaic at the block size the engine uses, and the fused
MSI wave loop must compile and fit one chip at the largest shape bucket
``chip_smoke.py`` reaches.  What the TPU compiler refuses here would
otherwise surface only on the chip.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dataplane import engine as E
from repro.kernels import ops as K

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to a persistent
    # cache but never read back without the chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


# Whole-trace batches of chip_smoke's two phases: 80 threads x 20k
# (YCSB-A) and 32 threads x 20k (TF); both tables have < 128 rows.
@pytest.mark.parametrize("batch", [1_600_000, 640_000])
@pytest.mark.parametrize("kernel", ["translate_lookup", "protect_check"])
def test_tcam_kernel_compiles_to_mosaic(one_chip, kernel, batch):
    lowered = K.lower_tcam(batch, 11, interpret=False,
                           sharding=one_chip)[kernel]
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


# (lanes, waves, slots per lane, blades, plane words + span, span): the
# per-axis maxima over the 70 wave-loop buckets that chip_smoke.py's
# phases A and B reached on a v5e chip, so it bounds every one of them.
REPLAY_BUCKET = (16, 16384, 4096, 8, 6224, 16)


def test_replay_compiles_at_largest_bucket(one_chip):
    g, L, s, nb, words, span = REPLAY_BUCKET

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stream = arg((g, L))
    args = ([arg(()), arg((), jnp.bool_)] + [stream] * 3
            + [arg((g, L), jnp.bool_)] + [stream] * 4
            + [arg((g, s, 4)), arg((g, s, span)), arg((g, 2 * nb, words))])
    compiled = E._replay.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES
