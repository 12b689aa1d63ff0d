"""What the switch's protection and translation stages answer for a
trace: the plain per-address semantics of ``ProtectionTable.check`` and
``GlobalAddressSpace.translate``, over the tables as mapping the trace's
arena leaves them, before its first access."""

from __future__ import annotations

import numpy as np

from benchmarks.chip.reference.types import Perm

PDID = 1  # the protection domain ``_map_arena`` maps the arena under


def expected(rack, trace) -> dict:
    """Per access of ``trace`` on the fresh reference ``rack``: its
    vaddr, whether protection allows it, and the translation entry that
    serves it as its exported row ``(base, log2, blade, pa_delta)``."""
    segs = rack._map_arena(trace)
    vaddrs = rack._to_vaddr_batch(segs, trace.offsets)
    need = np.where(np.asarray(trace.ops) != 0, int(Perm.WRITE),
                    int(Perm.READ))
    return {"vaddrs": vaddrs,
            "allow": _allow(rack.mmu.protection, vaddrs, need),
            "rows": _translation_rows(rack.mmu.gas, vaddrs)}


def _allow(prot, vaddrs, need) -> np.ndarray:
    """``check``: the first matching entry decides, by its permission."""
    allow = np.zeros(len(vaddrs), bool)
    decided = np.zeros(len(vaddrs), bool)
    for e in prot._entries.values():
        m = ~decided & (e.pdid == PDID) & (
            (vaddrs >> e.prefix_log2) == (e.prefix_base >> e.prefix_log2))
        allow[m] = (int(e.perm) & need[m]) != 0
        decided |= m
    return allow


def _translation_rows(gas, vaddrs) -> np.ndarray:
    """``translate``: the most specific outlier, else the home blade."""
    span_log2 = gas.blade_span.bit_length() - 1
    slot = (vaddrs - gas.va_origin) // gas.blade_span
    rows = np.full((len(vaddrs), 4), -1, np.int64)
    for b, spec in gas.blades.items():
        rows[slot == b] = (spec.va_base, span_log2, b, -spec.va_base)
    best = np.full(len(vaddrs), np.iinfo(np.int64).max)
    for e in gas.outliers:
        m = ((vaddrs >> e.prefix_log2) == (e.prefix_base >> e.prefix_log2)) \
            & (e.prefix_log2 < best)
        rows[m] = (e.prefix_base, e.prefix_log2, e.target_blade, e.pa_delta)
        best[m] = e.prefix_log2
    return rows
