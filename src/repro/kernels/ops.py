"""Public jit'd entry points for the Pallas kernels.

``interpret`` defaults follow the backend: on the CPU (the test suite,
``JAX_PLATFORMS=cpu``) the kernels execute in interpret mode; on the TPU
they compile to Mosaic.  Any other backend is refused rather than
silently interpreted.  All shapes are padded/validated here so kernel
bodies stay branch-free.
"""

from __future__ import annotations

import jax

from repro.kernels import directory_msi as _msi
from repro.kernels import flash_attention as _flash
from repro.kernels import paged_attention as _paged
from repro.kernels import range_match as _rm


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels lower to Mosaic on 'tpu' and run interpreted on "
        f"'cpu'; backend {backend!r} has neither (set JAX_PLATFORMS=cpu "
        f"or run on a TPU)")


def _tcam_kw(kw: dict) -> dict:
    kw.setdefault("interpret", _default_interpret())
    if kw["interpret"]:
        # Interpret mode pays Python-level cost per grid step: use a
        # large request block so big batches run in a handful of steps
        # (on TPU the default BLOCK_B matches XLA's 1-D operand tiling).
        kw.setdefault("block_b", 8192)
    return kw


def translate_lookup(vaddrs, table, **kw):
    return _rm.translate_lookup(vaddrs, table, **_tcam_kw(kw))


def protect_check(pdids, vaddrs, need, table, **kw):
    return _rm.protect_check(pdids, vaddrs, need, table, **_tcam_kw(kw))


def lower_tcam(batch: int, rows: int, **kw) -> dict:
    """The TCAM programs exactly as the two wrappers above would run them
    (see :func:`range_match.lower_tcam`)."""
    return _rm.lower_tcam(batch, rows, **_tcam_kw(kw))


def msi_transition(state, sharers, owner, slots, requesters, is_write, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _msi.msi_transition(state, sharers, owner, slots, requesters,
                               is_write, **kw)


def msi_transition_vectorized(state, sharers, owner, slots, requesters, is_write):
    return _msi.msi_transition_vectorized(
        state, sharers, owner, slots, requesters, is_write
    )


def paged_attention(q, kv_pages_k, kv_pages_v, block_tables, seq_lens, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _paged.paged_attention(
        q, kv_pages_k, kv_pages_v, block_tables, seq_lens, **kw
    )


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _flash.flash_attention(q, k, v, **kw)


build_transition_table = _msi.build_transition_table
split64_np = _rm.split64_np
NO_MATCH = _rm.NO_MATCH
