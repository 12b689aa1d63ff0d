"""YCSB core workload over memcached (Cooper et al., SoCC 2010): every
thread draws keys from YCSB's scrambled zipfian over the whole store,
``read_ratio`` reads and the rest updates.

The key chooser is YCSB's ``ScrambledZipfianGenerator``: a bounded
zipfian (Gray et al., SIGMOD 1994) over 10^10 items with constant 0.99
and YCSB's precomputed zeta, its draw hashed with FNV-1a 64 onto the
store's records, so that hot keys lie scattered over the store.  The
load phase inserts records in key order, so record ``k`` sits at byte
``k * record_bytes`` of the store, and an access touches the page that
holds it.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.gen import interleave

ITEM_COUNT = 10_000_000_000  # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302  # its zeta(ITEM_COUNT, 0.99)
THETA = 0.99  # ZipfianGenerator.ZIPFIAN_CONSTANT
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the value's 8 bytes, low
    byte first, then ``Math.abs`` of the signed result."""
    v = vals.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= FNV_PRIME
    return np.abs(h.view(np.int64))


def zipfian(rng, n: int) -> np.ndarray:
    """``ZipfianGenerator(0, ITEM_COUNT, 0.99, ZETAN).nextValue()``."""
    items = ITEM_COUNT + 1
    zeta2 = 1.0 + 0.5 ** THETA
    alpha = 1.0 / (1.0 - THETA)
    eta = (1.0 - (2.0 / items) ** (1.0 - THETA)) / (1.0 - zeta2 / ZETAN)
    u = rng.random(n)
    uz = u * ZETAN
    ret = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** THETA, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def scrambled_zipfian(rng, n: int, records: int) -> np.ndarray:
    """``ScrambledZipfianGenerator(0, records - 1).nextValue()``."""
    return fnvhash64(zipfian(rng, n)) % records


def generate(num_threads, accesses_per_thread, rng, *, read_ratio,
             store_mb, record_bytes) -> dict:
    arena = store_mb << 20
    records = arena // record_bytes
    ths, ops, offs = [], [], []
    for t in range(num_threads):
        n = accesses_per_thread
        key = scrambled_zipfian(rng, n, records)
        op = (rng.random(n) >= read_ratio).astype(np.int8)
        ths.append(np.full(n, t, np.int32))
        ops.append(op)
        offs.append(key * record_bytes)
    return interleave(ths, ops, offs, arena, arena)
