"""Least work of one call of each TCAM kernel (``kernels/range_match``).

The work is what the algorithm needs for ``n`` requests against a table
of ``rows`` entries, never the implementation's padding of either: each
request's operands are read once and its result written once, and the
table is read once.  Every field is an int32 on the device; a 64-bit
address is its (hi, lo) pair; protection's allow mask is one byte per
request.  Each request is matched against every row: ``compares`` counts
those prefix matches, for the record (no published int32-compare peak
exists, so the roofline is bound by bytes).
"""

from __future__ import annotations

I32 = 4


def translate_lookup(n: int, rows: int) -> dict:
    # request: vaddr (hi, lo); result: blade, row.
    # row: base (hi, lo), prefix log2, target blade.
    return {"bytes": n * (2 + 2) * I32 + rows * 4 * I32,
            "compares": n * rows}


def protect_check(n: int, rows: int) -> dict:
    # request: pdid, vaddr (hi, lo), needed permission; result: allow.
    # row: pdid, base (hi, lo), prefix log2, permission.
    return {"bytes": n * (4 * I32 + 1) + rows * 5 * I32,
            "compares": n * rows}


KERNELS = {"translate_lookup": translate_lookup,
           "protect_check": protect_check}
