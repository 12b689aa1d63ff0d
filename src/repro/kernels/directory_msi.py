"""In-network MSI directory transitions as a Pallas TPU kernel (§6.3).

The switch splits directory handling across two MAU stages: stage 1 holds
the directory entries and performs the lookup; stage 2 holds a
*materialized state-transition table* (trading memory for the compute an
MAU lacks) and decides the actions; the packet then *recirculates* so
stage 1 can write the updated entry.

The TPU adaptation keeps both the materialized transition table and the
staged structure, but fuses the write-back into the same kernel pass — a
Pallas kernel can read-modify-write VMEM, so recirculation is unnecessary
(recorded as an adaptation win in DESIGN.md §2).  Requests are processed
in batch order with a `fori_loop`, which preserves the switch's
packet-serialization semantics for requests that hit the same region.

Directory layout (the switch-SRAM constraint carries over: the whole
directory must fit the kernel's VMEM working set — Bounded Splitting §5 is
what makes that possible):
    state:   int32 [S]  (0=I, 1=S, 2=M)
    sharers: int32 [S]  (bitmap over <=32 compute blades)
    owner:   int32 [S]  (-1 if none)

Outputs per request:
    fetch_src:  -1 local hit, -2 home memory blade, >=0 fetch-from-owner
    inval_mask: sharer bitmap the egress multicast must invalidate
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Action codes in the materialized table.
FETCH_LOCAL, FETCH_MEM, FETCH_OWNER = 0, 1, 2
INV_NONE, INV_OTHERS, INV_OWNER = 0, 1, 2
SH_KEEP, SH_SET_ME, SH_OR_ME = 0, 1, 2
OW_KEEP, OW_SET_REQ, OW_CLEAR = 0, 1, 2

I, S, M = 0, 1, 2


def build_transition_table() -> np.ndarray:
    """Materialize all (state, is_write, is_owner, in_sharers) transitions.

    Rows indexed by ((state*2 + is_write)*2 + is_owner)*2 + in_sharers;
    columns = (new_state, fetch_kind, inval_kind, sharers_code, owner_code).
    This is the exact analogue of the paper's MAU-2 table.
    """
    tbl = np.zeros((24, 5), np.int32)

    def put(st, w, is_ow, in_sh, row):
        tbl[((st * 2 + w) * 2 + is_ow) * 2 + in_sh] = row

    for is_ow in (0, 1):
        for in_sh in (0, 1):
            # I + read -> S, fetch memory.
            put(I, 0, is_ow, in_sh, (S, FETCH_MEM, INV_NONE, SH_SET_ME, OW_CLEAR))
            # I + write -> M, fetch memory.
            put(I, 1, is_ow, in_sh, (M, FETCH_MEM, INV_NONE, SH_SET_ME, OW_SET_REQ))
    # S + read: local if already sharer else memory fetch; join sharers.
    for is_ow in (0, 1):
        put(S, 0, is_ow, 1, (S, FETCH_LOCAL, INV_NONE, SH_OR_ME, OW_CLEAR))
        put(S, 0, is_ow, 0, (S, FETCH_MEM, INV_NONE, SH_OR_ME, OW_CLEAR))
        # S + write: invalidate other sharers (multicast, parallel with the
        # memory fetch, the ~9us path of Fig. 8).
        put(S, 1, is_ow, 1, (M, FETCH_LOCAL, INV_OTHERS, SH_SET_ME, OW_SET_REQ))
        put(S, 1, is_ow, 0, (M, FETCH_MEM, INV_OTHERS, SH_SET_ME, OW_SET_REQ))
    for in_sh in (0, 1):
        # M + read @ owner: local.   M + read elsewhere: owner flush (~18us).
        put(M, 0, 1, in_sh, (M, FETCH_LOCAL, INV_NONE, SH_KEEP, OW_KEEP))
        put(M, 0, 0, in_sh, (S, FETCH_OWNER, INV_OWNER, SH_SET_ME, OW_CLEAR))
        # M + write @ owner: local.  M + write elsewhere: owner flush.
        put(M, 1, 1, in_sh, (M, FETCH_LOCAL, INV_NONE, SH_KEEP, OW_KEEP))
        put(M, 1, 0, in_sh, (M, FETCH_OWNER, INV_OWNER, SH_SET_ME, OW_SET_REQ))
    return tbl


def _msi_kernel(slots_ref, req_ref, write_ref, ttable_ref,
                state_in_ref, sharers_in_ref, owner_in_ref,
                state_ref, sharers_ref, owner_ref, fetch_ref, inval_ref):
    """Sequential (packet-order) MSI over one request batch.

    state/sharers/owner are carried as input_output_aliased VMEM buffers;
    the loop is the line-rate pipeline, one 'packet' per iteration.
    """
    # Initialize the aliased outputs from the inputs.
    state_ref[:] = state_in_ref[:]
    sharers_ref[:] = sharers_in_ref[:]
    owner_ref[:] = owner_in_ref[:]

    nreq = slots_ref.shape[0]

    def body(i, _):
        slot = slots_ref[i]
        req = req_ref[i]
        w = write_ref[i]
        me = jnp.int32(1) << req

        # --- MAU stage 1: directory lookup -------------------------------
        st = state_ref[slot]
        sh = sharers_ref[slot]
        ow = owner_ref[slot]

        # --- MAU stage 2: materialized transition table ------------------
        is_ow = (ow == req).astype(jnp.int32)
        in_sh = (sh >> req) & 1
        idx = ((st * 2 + w) * 2 + is_ow) * 2 + in_sh
        new_state = ttable_ref[idx, 0]
        fetch_kind = ttable_ref[idx, 1]
        inval_kind = ttable_ref[idx, 2]
        sh_code = ttable_ref[idx, 3]
        ow_code = ttable_ref[idx, 4]

        # Decode actions.
        fetch = jnp.where(
            fetch_kind == FETCH_LOCAL,
            jnp.int32(-1),
            jnp.where(fetch_kind == FETCH_MEM, jnp.int32(-2), ow),
        )
        inval = jnp.where(
            inval_kind == INV_OTHERS,
            sh & ~me,
            jnp.where(inval_kind == INV_OWNER, jnp.int32(1) << ow, jnp.int32(0)),
        )
        new_sh = jnp.where(
            sh_code == SH_SET_ME, me, jnp.where(sh_code == SH_OR_ME, sh | me, sh)
        )
        new_ow = jnp.where(
            ow_code == OW_SET_REQ,
            req,
            jnp.where(ow_code == OW_CLEAR, jnp.int32(-1), ow),
        )

        # --- write-back (fused recirculation) ----------------------------
        state_ref[slot] = new_state
        sharers_ref[slot] = new_sh
        owner_ref[slot] = new_ow
        fetch_ref[i] = fetch
        inval_ref[i] = inval
        return 0

    jax.lax.fori_loop(0, nreq, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def msi_transition(state, sharers, owner, slots, requesters, is_write,
                   *, interpret: bool):
    """Batched in-network MSI transitions (fused two-stage pipeline).

    Args mirror ref.msi_transition_ref.  The whole directory plus the
    24-row transition table resides in VMEM — the switch-SRAM analogue.
    """
    ttable = jnp.asarray(build_transition_table())
    s = state.shape[0]
    b = slots.shape[0]
    out = pl.pallas_call(
        _msi_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots
            pl.BlockSpec(memory_space=pl.ANY),  # requesters
            pl.BlockSpec(memory_space=pl.ANY),  # is_write
            pl.BlockSpec(memory_space=pl.ANY),  # ttable
            pl.BlockSpec(memory_space=pl.ANY),  # state_in
            pl.BlockSpec(memory_space=pl.ANY),  # sharers_in
            pl.BlockSpec(memory_space=pl.ANY),  # owner_in
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s,), jnp.int32),  # state
            jax.ShapeDtypeStruct((s,), jnp.int32),  # sharers
            jax.ShapeDtypeStruct((s,), jnp.int32),  # owner
            jax.ShapeDtypeStruct((b,), jnp.int32),  # fetch_src
            jax.ShapeDtypeStruct((b,), jnp.int32),  # inval_mask
        ],
        interpret=interpret,
    )(
        slots.astype(jnp.int32),
        requesters.astype(jnp.int32),
        is_write.astype(jnp.int32),
        ttable,
        state.astype(jnp.int32),
        sharers.astype(jnp.int32),
        owner.astype(jnp.int32),
    )
    return out


def msi_transition_vectorized(state, sharers, owner, slots, requesters,
                              is_write):
    """Beyond-paper variant: conflict-free batches (all `slots` distinct)
    processed fully vectorized — no packet serialization.  Pure jnp (the
    whole computation is element-wise gathers/scatters, which XLA already
    fuses well); used by the serving engine where the scheduler guarantees
    one request per page per step.
    """
    ttable = jnp.asarray(build_transition_table())
    slots = slots.astype(jnp.int32)
    req = requesters.astype(jnp.int32)
    w = is_write.astype(jnp.int32)
    me = jnp.int32(1) << req
    st = state[slots]
    sh = sharers[slots]
    ow = owner[slots]
    is_ow = (ow == req).astype(jnp.int32)
    in_sh = (sh >> req) & 1
    idx = ((st * 2 + w) * 2 + is_ow) * 2 + in_sh
    row = ttable[idx]
    fetch = jnp.where(
        row[:, 1] == FETCH_LOCAL, -1, jnp.where(row[:, 1] == FETCH_MEM, -2, ow)
    )
    inval = jnp.where(
        row[:, 2] == INV_OTHERS, sh & ~me,
        jnp.where(row[:, 2] == INV_OWNER, jnp.int32(1) << ow, 0),
    )
    new_sh = jnp.where(
        row[:, 3] == SH_SET_ME, me, jnp.where(row[:, 3] == SH_OR_ME, sh | me, sh)
    )
    new_ow = jnp.where(row[:, 4] == OW_SET_REQ, req,
                       jnp.where(row[:, 4] == OW_CLEAR, -1, ow))
    new_state = state.at[slots].set(row[:, 0])
    new_sharers = sharers.at[slots].set(new_sh)
    new_owner = owner.at[slots].set(new_ow)
    return new_state, new_sharers, new_owner, fetch, inval
