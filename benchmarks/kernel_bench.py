"""Pallas data-plane kernel microbench.

Each row is labelled with the platform the run used and how the kernel
ran there: ``interpret`` (CPU), ``mosaic`` (TPU) or ``xla``.  Interpret
wall times are not TPU times; they track relative cost and regression.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_us
from repro.compile_cache import enable_compile_cache
from repro.kernels import ops as K


def main() -> None:
    enable_compile_cache()
    platform = jax.devices()[0].platform
    pallas = f"{platform}/{'interpret' if K._default_interpret() else 'mosaic'}"
    xla = f"{platform}/xla"
    rng = np.random.default_rng(0)
    # range match: 1024 requests x 512-row table
    tbl = np.array([((1 << 40) + (i << 36), 36, i, 0) for i in range(8)],
                   np.int64)
    v = (1 << 40) + rng.integers(0, 8 << 36, 1024).astype(np.int64)
    us = time_us(lambda: K.translate_lookup(v, tbl))
    emit("kernel/translate_1024x8", us, pallas)

    # MSI transitions: 512 requests on a 4096-slot directory
    s = 4096
    state = jnp.asarray(rng.integers(0, 3, s), jnp.int32)
    owner = jnp.where(state == 2, rng.integers(0, 8, s), -1).astype(jnp.int32)
    sharers = jnp.where(state == 2, 1 << jnp.maximum(owner, 0),
                        jnp.where(state == 1, 3, 0)).astype(jnp.int32)
    slots = jnp.asarray(rng.integers(0, s, 512), jnp.int32)
    req = jnp.asarray(rng.integers(0, 8, 512), jnp.int32)
    w = jnp.asarray(rng.integers(0, 2, 512), jnp.int32)
    us = time_us(lambda: jax.block_until_ready(
        K.msi_transition(state, sharers, owner, slots, req, w)))
    emit("kernel/msi_seq_512x4096", us, pallas)
    us = time_us(lambda: jax.block_until_ready(
        K.msi_transition_vectorized(state, sharers, owner,
                                    slots[:256], req[:256], w[:256])))
    emit("kernel/msi_vec_256x4096", us, xla)

    # paged attention: B=8, Hq=8, Hkv=2, D=64, 16-token pages, 8 pages
    q = jnp.asarray(rng.standard_normal((8, 8, 64)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((64, 16, 2, 64)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((64, 16, 2, 64)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, 64, (8, 8)), jnp.int32)
    sl = jnp.full((8,), 100, jnp.int32)
    us = time_us(lambda: jax.block_until_ready(
        K.paged_attention(q, kp, vp, bt, sl)))
    emit("kernel/paged_attn_b8", us, pallas)

    # flash attention: 1x4x256x64
    qq = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    us = time_us(lambda: jax.block_until_ready(
        K.flash_attention(qq, qq, qq, block_q=128, block_k=128)))
    emit("kernel/flash_attn_256", us, pallas)


if __name__ == "__main__":
    main()
