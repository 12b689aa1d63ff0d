"""The benchmark's trace generators and pools cannot drift silently."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import pool as P  # noqa: E402
from benchmarks.chip.gen import ycsb  # noqa: E402


def digest(d) -> str:
    h = hashlib.sha256()
    for k in ("threads", "ops", "offsets"):
        h.update(np.ascontiguousarray(d[k]).tobytes())
    h.update(f"{d['arena_bytes']},{d['shared_bytes']}".encode())
    return h.hexdigest()


@pytest.mark.parametrize("params,want", [
    (dict(read_ratio=0.5, store_mb=2048, record_bytes=1024),
     "ad5214b707732c30d0a9cf83cd85033f9319f178439998aaf48e3b262d2d1c61"),
    (dict(read_ratio=1.0, store_mb=64, record_bytes=1024),
     "bf6b3273e8ba75863d2d23c43ddb1790244ef221cbbbe24865b71a3a3a10f0fd"),
])
def test_generator_digest_pinned(params, want):
    d = ycsb.generate(4, 50, np.random.default_rng(12345), **params)
    assert digest(d) == want


def _fnv1a64_scalar(v: int) -> int:
    """YCSB's ``Utils.fnvhash64`` as Java runs it, one value at a time."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & (2**64 - 1)
    signed = h - 2**64 if h >= 2**63 else h
    return abs(signed)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 2, 255, 256, 12345678901, 10_000_000_000, 2**62 + 3]
    got = ycsb.fnvhash64(np.array(vals, np.int64))
    assert got.tolist() == [_fnv1a64_scalar(v) for v in vals]


def test_zipfian_head_matches_ycsb_constants():
    """Item 0 takes 1/zeta(10^10, 0.99) of the draws and item 1
    0.5**0.99 of that, as YCSB's generator gives them."""
    z = ycsb.zipfian(np.random.default_rng(7), 400_000)
    assert z.min() == 0 and z.max() <= ycsb.ITEM_COUNT
    p0 = 1 / ycsb.ZETAN
    assert abs(np.mean(z == 0) - p0) < 5 * np.sqrt(p0 / 400_000)
    p1 = 0.5 ** ycsb.THETA / ycsb.ZETAN
    assert abs(np.mean(z == 1) - p1) < 5 * np.sqrt(p1 / 400_000)


def _load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "benchmarks/chip" / kind
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("config,traffic", [
    ("mind.ycsb_a.8x10", "short_jobs"),
    ("mind.ycsb_a.8x10", "memblade_fault"),
    ("mind.ycsb_a.8x10", "long_jobs"),
])
def test_pool_same_work_for_every_seed(config, traffic):
    config = dict(_load("configs", config), accesses_per_thread=20)
    traffic = _load("traffic", traffic)
    traffic = dict(traffic, accesses_per_thread=traffic[
        "accesses_per_thread"] and 10)
    seeds = (0, 2**31 + 12345, 2**40 + 7)
    pools = [P.make_pool(config, traffic, s) for s in seeds]
    shapes = {tuple((len(j), j.arena_bytes, tuple((k, b) for _, k, b in
                                                  j.faults)) for j in p)
              for p in pools}
    assert len(shapes) == 1
    assert len({digest(vars(p[0])) for p in pools}) == len(seeds)
    again = P.make_pool(config, traffic, seeds[1])
    assert [digest(vars(j)) for j in again] == [digest(vars(j))
                                                for j in pools[1]]
