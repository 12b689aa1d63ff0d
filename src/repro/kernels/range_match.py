"""TCAM-style range match as a Pallas TPU kernel (§4.2, §4.4).

The switch matches each access's (PDID, vaddr) against power-of-two range
entries *in parallel* and takes the longest-prefix match.  On TPU the
match-action table lives in VMEM (the SRAM/TCAM analogue) and a batch of
access descriptors is matched per invocation: a [block_b, T] comparison
matrix is materialized in VMEM and reduced with a masked min over
(prefix length, row) keys (LPM semantics).

Request vectors are 1-D, and XLA tiles a 1-D int32 array on the TPU in
runs of 1024 elements, so a request block must be a multiple of
``BLOCK_B`` for Mosaic to accept the operand layout; the wrappers pad
the batch to the block.

64-bit virtual addresses are carried as (hi, lo) int32 pairs because the
TPU vector unit is 32-bit and JAX runs with x64 disabled; ``split64_np``
performs the host-side split.

Table row layout (see core/switch.py::export_dataplane_tables):
    translate table: [T, 4] = (prefix_base, prefix_log2, target_blade, pa_delta)
    protect   table: [T, 4] = (pdid, prefix_base, prefix_log2, perm)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NO_MATCH = 0x7FFFFFFF
_LANES = 128
BLOCK_B = 1024  # XLA's 1-D int32 tile on the TPU; smaller blocks are refused
_LPM_STRIDE = 1 << 20  # > max table rows; makes (log2, row) keys unique


def split64_np(x) -> tuple[np.ndarray, np.ndarray]:
    """Host-side int64 -> (hi32, lo32) int32 pair."""
    x = np.asarray(x, dtype=np.int64)
    hi = (x >> 32).astype(np.int32)
    lo = (x & np.int64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    return hi, lo


def join64_np(hi, lo) -> np.ndarray:
    return (np.asarray(hi, np.int64) << 32) | (
        np.asarray(lo, np.int64) & np.int64(0xFFFFFFFF)
    )


def _prefix_eq(vhi, vlo, bhi, blo, log2):
    """(vaddr >> log2) == (base >> log2) on split 32-bit halves."""
    l_lo = jnp.clip(log2, 0, 31)
    lo_mask = jnp.where(log2 >= 32, jnp.int32(0), jnp.int32(-1) << l_lo)
    hi_shift = jnp.clip(log2 - 32, 0, 31)
    hi_mask = jnp.where(log2 >= 32, jnp.int32(-1) << hi_shift, jnp.int32(-1))
    lo_ok = (vlo & lo_mask) == (blo & lo_mask)
    hi_ok = (vhi & hi_mask) == (bhi & hi_mask)
    return jnp.logical_and(lo_ok, hi_ok)


# --------------------------------------------------------------------- #
# Kernel bodies.
# --------------------------------------------------------------------- #
def _translate_kernel(vhi_ref, vlo_ref, tbl_hi_ref, tbl_lo_ref, tbl_log2_ref,
                      tbl_blade_ref, nrows_ref, blade_ref, idx_ref):
    """One block of requests vs. the whole translate table (VMEM)."""
    vhi = vhi_ref[:]  # [B]
    vlo = vlo_ref[:]
    bhi = tbl_hi_ref[:]  # [T]
    blo = tbl_lo_ref[:]
    log2 = tbl_log2_ref[:]
    blade = tbl_blade_ref[:]
    n = nrows_ref[0]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (1, bhi.shape[0]), 1)
    valid = t_idx < n  # padded rows never match
    m = _prefix_eq(vhi[:, None], vlo[:, None], bhi[None, :], blo[None, :],
                   log2[None, :])
    m = jnp.logical_and(m, valid)
    # LPM: smallest log2 wins; row index breaks ties deterministically.
    # The key packs (log2, row) so its min names the winning row, and a
    # one-hot mask on the min recovers the row's blade with a masked sum
    # (Mosaic lowers int32 min/sum lane reductions, not argmin/gather).
    big = jnp.int32(1 << 30)
    key = jnp.where(m, log2[None, :] * jnp.int32(_LPM_STRIDE) + t_idx, big)
    kmin = jnp.min(key, axis=1)
    matched = kmin < big
    hit = key == kmin[:, None]
    best_blade = jnp.sum(jnp.where(hit, blade[None, :], 0), axis=1)
    blade_ref[:] = jnp.where(matched, best_blade, jnp.int32(-1))
    idx_ref[:] = jnp.where(matched, kmin & jnp.int32(_LPM_STRIDE - 1),
                           jnp.int32(NO_MATCH))


def _protect_kernel(pdid_ref, vhi_ref, vlo_ref, need_ref, tbl_pdid_ref,
                    tbl_hi_ref, tbl_lo_ref, tbl_log2_ref, tbl_perm_ref,
                    nrows_ref, allow_ref):
    pdid = pdid_ref[:]
    vhi = vhi_ref[:]
    vlo = vlo_ref[:]
    need = need_ref[:]  # permission bits needed (1=R, 2=W)
    t_pdid = tbl_pdid_ref[:]
    bhi = tbl_hi_ref[:]
    blo = tbl_lo_ref[:]
    log2 = tbl_log2_ref[:]
    perm = tbl_perm_ref[:]
    n = nrows_ref[0]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (1, bhi.shape[0]), 1)
    valid = t_idx < n
    m = _prefix_eq(vhi[:, None], vlo[:, None], bhi[None, :], blo[None, :],
                   log2[None, :])
    m = jnp.logical_and(m, pdid[:, None] == t_pdid[None, :])
    m = jnp.logical_and(m, valid)
    # Parallel TCAM semantics: any matching entry whose PC covers the
    # requested access admits it; a miss rejects (§4.2).
    ok = jnp.logical_and(m, (perm[None, :] & need[:, None]) == need[:, None])
    allow_ref[:] = jnp.any(ok, axis=1)


# --------------------------------------------------------------------- #
# pallas_call wrappers with BlockSpec tiling.
# --------------------------------------------------------------------- #
def _pad_rows_np(tbl: np.ndarray, multiple: int = _LANES) -> np.ndarray:
    t = tbl.shape[0]
    pad = (-t) % multiple
    if pad:
        tbl = np.pad(tbl, ((0, pad), (0, 0)))
    return tbl


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _translate_call(vhi, vlo, bhi, blo, log2, blade, nrows, *, block_b, interpret):
    b = vhi.shape[0]
    t = bhi.shape[0]
    grid = (b // block_b,)
    return pl.pallas_call(
        _translate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((t,), lambda i: (0,)),  # whole table resident in VMEM
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ],
        interpret=interpret,
    )(vhi, vlo, bhi, blo, log2, blade, nrows)


def translate_lookup(vaddrs, table, *, interpret: bool,
                     block_b: int = BLOCK_B):
    """Batch-translate virtual addresses.

    Args:
      vaddrs: int64 host array [B] of virtual addresses.
      table: int64 host array [T, 4], outliers (longest prefixes) first.
    Returns:
      (blade int32 [B], row_idx int32 [B]); row_idx==NO_MATCH => fault.
    """
    vaddrs = np.asarray(vaddrs, np.int64)
    table = np.asarray(table, np.int64)
    b = vaddrs.shape[0]
    pad_b = (-b) % block_b
    vaddrs = np.pad(vaddrs, (0, pad_b))
    t_orig = table.shape[0]
    table = _pad_rows_np(table)
    vhi, vlo = split64_np(vaddrs)
    bhi, blo = split64_np(table[:, 0])
    log2 = table[:, 1].astype(np.int32)
    blade = table[:, 2].astype(np.int32)
    nrows = np.array([t_orig], np.int32)
    out = _translate_call(vhi, vlo, bhi, blo, log2, blade, nrows,
                          block_b=block_b, interpret=interpret)
    return np.asarray(out[0][:b]), np.asarray(out[1][:b])


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _protect_call(pdids, vhi, vlo, need, t_pdid, bhi, blo, log2, perm, nrows,
                  *, block_b, interpret):
    b = vhi.shape[0]
    t = bhi.shape[0]
    grid = (b // block_b,)
    return pl.pallas_call(
        _protect_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.bool_),
        interpret=interpret,
    )(pdids, vhi, vlo, need, t_pdid, bhi, blo, log2, perm, nrows)


def protect_check(pdids, vaddrs, need, table, *, interpret: bool,
                  block_b: int = BLOCK_B):
    """Batch protection check.

    Args:
      pdids: int32 [B]; vaddrs: int64 [B]; need: int32 [B] permission bits.
      table: int64 [T, 4] = (pdid, base, log2, perm).
    Returns: bool [B] allow mask.
    """
    pdids = np.asarray(pdids, np.int32)
    vaddrs = np.asarray(vaddrs, np.int64)
    need = np.asarray(need, np.int32)
    table = np.asarray(table, np.int64)
    b = vaddrs.shape[0]
    pad_b = (-b) % block_b
    pdids = np.pad(pdids, (0, pad_b))
    vaddrs = np.pad(vaddrs, (0, pad_b))
    need = np.pad(need, (0, pad_b))
    t_orig = table.shape[0]
    table = _pad_rows_np(table)
    vhi, vlo = split64_np(vaddrs)
    bhi, blo = split64_np(table[:, 1])
    t_pdid = table[:, 0].astype(np.int32)
    log2 = table[:, 2].astype(np.int32)
    perm = table[:, 3].astype(np.int32)
    nrows = np.array([t_orig], np.int32)
    allow = _protect_call(pdids, vhi, vlo, need, t_pdid, bhi, blo, log2, perm,
                          nrows, block_b=block_b, interpret=interpret)
    return np.asarray(allow[:b])


def lower_tcam(batch: int, rows: int, *, interpret: bool,
               block_b: int = BLOCK_B, sharding=None) -> dict:
    """Both TCAM programs as the wrappers above call them for ``batch``
    requests against a ``rows``-row table: lowered, not compiled.
    ``sharding`` places the operands, e.g. on a described chip for an
    ahead-of-time compile."""
    b = batch + (-batch) % block_b
    t = rows + (-rows) % _LANES

    def vec(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)

    kw = dict(block_b=block_b, interpret=interpret)
    return {
        "translate_lookup": _translate_call.lower(
            vec(b), vec(b), *[vec(t)] * 4, vec(1), **kw),
        "protect_check": _protect_call.lower(
            *[vec(b)] * 4, *[vec(t)] * 5, vec(1), **kw),
    }
