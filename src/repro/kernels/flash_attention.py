"""Blocked causal (flash) attention, Pallas TPU (prefill hot path).

Standard FlashAttention-2 style tiling adapted to TPU: grid
(B, H, S/bq, S/bk) with the key-block walk innermost; (m, l, acc) carried
in VMEM scratch across key blocks; fully-masked key blocks are skipped
(causal schedule), halving prefill FLOPs.

Block shapes default to MXU-aligned (128) tiles; the VMEM working set per
step is q[bq,D] + k[bk,D] + v[bk,D] + acc[bq,D] — comfortably < 16 MB for
D <= 256 at the defaults.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal schedule: skip key blocks strictly above the diagonal.
    run = (not causal) or (ik * bk <= iq * bq + (bq - 1))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool):
    """[B, H, S, D] blocked attention.  S must divide by block sizes (the
    caller pads); K/V may have fewer heads (GQA) — repeat before calling or
    pass Hkv == H."""
    b, h, s, d = q.shape
    assert k.shape == v.shape and k.shape[0] == b and k.shape[3] == d
    hk = k.shape[1]
    assert h % hk == 0
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    eff_scale = float(scale) if scale is not None else float(1.0 / (d ** 0.5))

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, scale=eff_scale, causal=causal
    )
    grid = (b, h, s // bq, s // bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
