"""Pallas kernels vs pure-jnp/numpy oracles: shape/dtype sweeps."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops as K
from repro.kernels import range_match
from repro.kernels import ref as R
from repro.kernels.directory_msi import build_transition_table


# ------------------------------------------------------------------ #
# interpret mode: chosen by backend, never by default
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_default_interpret_follows_backend(backend, interpret, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert K._default_interpret() is interpret


def test_default_interpret_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        K._default_interpret()


def test_raw_tcam_entry_points_require_interpret():
    v = np.zeros(4, np.int64)
    with pytest.raises(TypeError, match="interpret"):
        range_match.translate_lookup(v, _toy_translate_table())


# ------------------------------------------------------------------ #
# range_match
# ------------------------------------------------------------------ #
def _toy_translate_table(nblades=4, span_log2=36, origin=1 << 40):
    rows = [((origin + (3 << 36)) + (5 << 20), 20, 2, 123)]  # outlier
    for i in range(nblades):
        rows.append((origin + (i << span_log2), span_log2, i, 0))
    return np.array(rows, np.int64)


@pytest.mark.parametrize("n", [1, 7, 256, 1000])
def test_translate_matches_ref(n, rng):
    tbl = _toy_translate_table()
    v = (1 << 40) + rng.integers(0, 4 << 36, n).astype(np.int64)
    v[0] = (1 << 40) + (3 << 36) + (5 << 20) + 777  # outlier hit
    blade, idx = K.translate_lookup(v, tbl)
    rb, ri = R.translate_lookup_ref(v, tbl)
    np.testing.assert_array_equal(blade, rb)
    np.testing.assert_array_equal(idx, ri)


def test_translate_miss_faults(rng):
    tbl = _toy_translate_table(nblades=2)
    v = np.array([(1 << 40) + (3 << 36) + 5], np.int64)  # blade 3 absent
    blade, idx = K.translate_lookup(v, tbl)
    assert blade[0] == -1 or idx[0] == R.NO_MATCH or blade[0] == 2
    rb, ri = R.translate_lookup_ref(v, tbl)
    np.testing.assert_array_equal(blade, rb)


@pytest.mark.parametrize("t_rows,n", [(3, 64), (20, 300)])
def test_protect_matches_ref(t_rows, n, rng):
    base0 = 1 << 40
    rows = []
    for i in range(t_rows):
        rows.append((rng.integers(1, 4), base0 + int(rng.integers(0, 64)) * (1 << 16),
                     int(rng.integers(14, 22)), int(rng.integers(1, 4))))
    tbl = np.array(rows, np.int64)
    pd = rng.integers(1, 4, n).astype(np.int32)
    need = rng.integers(1, 3, n).astype(np.int32)
    va = base0 + rng.integers(0, 64 << 16, n).astype(np.int64)
    got = K.protect_check(pd, va, need, tbl)
    want = R.protect_check_ref(pd, va, need, tbl)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ #
# directory_msi
# ------------------------------------------------------------------ #
def _random_directory(rng, s, nblades=4):
    state = rng.integers(0, 3, s).astype(np.int32)
    owner = np.where(state == 2, rng.integers(0, nblades, s), -1).astype(np.int32)
    sharers = np.where(
        state == 2, 1 << np.maximum(owner, 0),
        np.where(state == 1, rng.integers(1, 1 << nblades, s), 0),
    ).astype(np.int32)
    return state, sharers, owner


@pytest.mark.parametrize("s,b", [(16, 40), (128, 500)])
def test_msi_sequential_matches_ref(s, b, rng):
    state, sharers, owner = _random_directory(rng, s)
    slots = rng.integers(0, s, b).astype(np.int32)
    req = rng.integers(0, 4, b).astype(np.int32)
    w = rng.integers(0, 2, b).astype(np.int32)
    got = K.msi_transition(jnp.array(state), jnp.array(sharers),
                           jnp.array(owner), jnp.array(slots),
                           jnp.array(req), jnp.array(w))
    want = R.msi_transition_ref(state, sharers, owner, slots, req, w)
    for g, r_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), r_)


def test_msi_vectorized_matches_ref_distinct_slots(rng):
    s = 64
    state, sharers, owner = _random_directory(rng, s)
    slots = rng.permutation(s)[:32].astype(np.int32)
    req = rng.integers(0, 4, 32).astype(np.int32)
    w = rng.integers(0, 2, 32).astype(np.int32)
    got = K.msi_transition_vectorized(jnp.array(state), jnp.array(sharers),
                                      jnp.array(owner), jnp.array(slots),
                                      jnp.array(req), jnp.array(w))
    want = R.msi_transition_ref(state, sharers, owner, slots, req, w)
    for g, r_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), r_)


def test_transition_table_is_total():
    tbl = build_transition_table()
    assert tbl.shape == (24, 5)
    assert (tbl[:, 0] <= 2).all() and (tbl[:, 0] >= 0).all()


# ------------------------------------------------------------------ #
# paged attention
# ------------------------------------------------------------------ #
@pytest.mark.parametrize(
    "b,hq,hkv,d,page,maxp,dtype",
    [
        (2, 4, 1, 32, 8, 4, np.float32),
        (3, 8, 2, 64, 16, 6, np.float32),
        (1, 4, 4, 128, 16, 3, np.float32),
        (2, 8, 2, 64, 16, 4, np.float32),
    ],
)
def test_paged_attention_matches_ref(b, hq, hkv, d, page, maxp, dtype, rng):
    p = maxp * b + 2
    q = rng.standard_normal((b, hq, d)).astype(dtype)
    kp = rng.standard_normal((p, page, hkv, d)).astype(dtype)
    vp = rng.standard_normal((p, page, hkv, d)).astype(dtype)
    bt = np.zeros((b, maxp), np.int32)
    sl = np.zeros(b, np.int32)
    pool = list(range(p))
    for i in range(b):
        n = int(rng.integers(1, maxp + 1))
        pages = [pool.pop() for _ in range(n)]
        bt[i, :n] = pages
        sl[i] = (n - 1) * page + int(rng.integers(1, page + 1))
    out = np.asarray(K.paged_attention(jnp.array(q), jnp.array(kp),
                                       jnp.array(vp), jnp.array(bt),
                                       jnp.array(sl)))
    bt_ref = bt.copy()
    for i in range(b):
        n = int(np.ceil(sl[i] / page))
        bt_ref[i, n:] = -1
    ref = R.paged_attention_ref(q, kp, vp, bt_ref, sl)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------------ #
# flash attention
# ------------------------------------------------------------------ #
@pytest.mark.parametrize(
    "b,h,hk,s,d,bq,bk,causal",
    [
        (2, 4, 4, 128, 64, 64, 64, True),
        (1, 8, 2, 256, 32, 128, 128, True),
        (2, 2, 1, 64, 128, 32, 32, True),
        (1, 4, 4, 128, 64, 64, 64, False),
    ],
)
def test_flash_attention_matches_ref(b, h, hk, s, d, bq, bk, causal, rng):
    q = rng.standard_normal((b, h, s, d)).astype(np.float32) * 0.5
    k = rng.standard_normal((b, hk, s, d)).astype(np.float32) * 0.5
    v = rng.standard_normal((b, hk, s, d)).astype(np.float32)
    out = np.asarray(K.flash_attention(jnp.array(q), jnp.array(k),
                                       jnp.array(v), causal=causal,
                                       block_q=bq, block_k=bk))
    kr, vr = np.repeat(k, h // hk, 1), np.repeat(v, h // hk, 1)
    ref = np.asarray(R.flash_attention_ref(q, kr, vr, causal=causal))
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
    out = K.flash_attention(q, k, v, block_q=32, block_k=32)
    ref = R.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0.1, atol=0.1)
