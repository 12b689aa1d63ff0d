"""Trace generators of the benchmark, one module per generator name.

Copies of the repository's §7 generators, with the seed taken as an
argument so that ``--seed`` reaches every draw.
Each module exposes ``generate(num_threads, accesses_per_thread, rng,
**params) -> dict`` of numpy arrays ``threads``, ``ops``, ``offsets``
and ints ``arena_bytes``, ``shared_bytes``.  They import nothing of the
program, so a change to the program cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

PAGE_SIZE = 4096


def interleave(ths, ops, offs, arena, shared_bytes) -> dict:
    """Round-robin interleave of per-thread streams (approximates
    concurrent execution; a random permutation would break per-thread
    streaming)."""
    th = np.concatenate(ths)
    op = np.concatenate(ops)
    off = np.concatenate(offs)
    order = np.argsort(np.concatenate([np.arange(len(t)) for t in ths]),
                       kind="stable")
    return dict(threads=th[order], ops=op[order], offsets=off[order],
                arena_bytes=int(arena), shared_bytes=int(shared_bytes))
