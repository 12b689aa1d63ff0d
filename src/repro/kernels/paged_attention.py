"""Paged decode attention over the disaggregated KV pool (Pallas TPU).

This is the perf-critical data path of the MIND-on-TPU adaptation: decode
reads KV pages that live in the pooled ("memory blade") HBM through the
page table that MIND's translation layer produced.  The kernel is the TPU
analogue of the RDMA page fetch + compute pipeline:

  * ``block_tables`` (the per-sequence page table) rides in SMEM as a
    scalar-prefetch operand — exactly how the switch keeps translation
    metadata in fast memory off the data path;
  * each grid step DMAs one physical KV page HBM->VMEM via the BlockSpec
    index_map (the "one-sided read");
  * online softmax accumulates in VMEM scratch across the page-walk grid
    dimension, so a page is touched exactly once (no false refetches).

Layouts:
  q:            [B, Hkv, G, D]   (G = query heads per KV head, GQA)
  k/v pool:     [P, page, Hkv, D]
  block_tables: int32 [B, maxp]  (pad with 0; masked via seq_lens)
  seq_lens:     int32 [B]
  out:          [B, Hkv, G, D]

Grid: (B, Hkv, maxp) with the page walk innermost (sequential on TPU, so
VMEM scratch carries the softmax state between pages).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(
    # scalar-prefetch operands (SMEM)
    block_tables_ref,  # int32 [B, maxp]
    seq_lens_ref,  # int32 [B]
    # VMEM blocks
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, page, 1, D]
    v_ref,  # [1, page, 1, D]
    o_ref,  # [1, 1, G, D]
    # VMEM scratch (persists across the page-walk grid dim)
    m_ref,  # [G, 1] running max
    l_ref,  # [G, 1] running denom
    acc_ref,  # [G, D] running numerator
    *,
    page_size: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    maxp = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    page_start = j * page_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        k = k_ref[0, :, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, :, 0].astype(jnp.float32)  # [page, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, page]
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < seq_len, logits, NEG_INF)

        m_prev = m_ref[:]  # [G, 1]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)  # [G, page]
        alpha = jnp.exp(m_prev - m_new)  # [G, 1]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(j == maxp - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q, kv_pages_k, kv_pages_v, block_tables, seq_lens, *,
                    interpret: bool, scale: float | None = None):
    """Decode attention over the paged pool.

    Args:
      q: [B, Hq, D] (Hq = Hkv * G) or [B, Hkv, G, D].
      kv_pages_k / kv_pages_v: [P, page, Hkv, D].
      block_tables: int32 [B, maxp]; entries are physical page ids; padded
        entries MUST be valid indices (use 0) and are masked by seq_lens.
      seq_lens: int32 [B].
    Returns: attention output with the same leading layout as q.
    """
    p, page_size, hkv, d = kv_pages_k.shape
    squeeze = q.ndim == 3
    if squeeze:
        b, hq, _ = q.shape
        g = hq // hkv
        q4 = q.reshape(b, hkv, g, d)
    else:
        q4 = q
        b = q4.shape[0]
        g = q4.shape[2]
    maxp = block_tables.shape[1]
    eff_scale = float(scale) if scale is not None else float(1.0 / (d ** 0.5))

    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, scale=eff_scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, maxp),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b_, h, j, bt, sl: (b_, h, 0, 0)),
                pl.BlockSpec(
                    (1, page_size, 1, d),
                    lambda b_, h, j, bt, sl: (bt[b_, j], 0, h, 0),
                ),
                pl.BlockSpec(
                    (1, page_size, 1, d),
                    lambda b_, h, j, bt, sl: (bt[b_, j], 0, h, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda b_, h, j, bt, sl: (b_, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q4, kv_pages_k, kv_pages_v)
    if squeeze:
        return out.reshape(b, hkv * g, d)
    return out
