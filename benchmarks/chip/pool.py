"""The job pool of a cell: traces and fault plans drawn from the seed.

A cell's traffic file says how many jobs the pool holds, their size and
the faults each one carries; the configuration's file says the rack and
the generator (``gen/<generator>.py``) with its parameters.  Every job
of a pool has the same size, so two seeds give the same work in
different draws.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field

import numpy as np

SEED_MASK = (1 << 64) - 1


@dataclass
class Job:
    """One replay job: a trace (the generator's arrays) and its faults,
    each fault as ``(access index, kind, memory blade)``."""

    name: str
    threads: np.ndarray
    ops: np.ndarray
    offsets: np.ndarray
    arena_bytes: int
    shared_bytes: int
    faults: list = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.ops)


def make_pool(config: dict, traffic: dict, seed: int) -> list[Job]:
    """The cell's jobs for ``seed``: job ``j`` is drawn from the seed
    sequence ``(seed, j)``."""
    gen = importlib.import_module(f"benchmarks.chip.gen.{config['generator']}")
    rack = config["rack"]
    nthreads = rack["num_compute_blades"] * rack["threads_per_blade"]
    apt = traffic["accesses_per_thread"] or config["accesses_per_thread"]
    jobs = []
    for j in range(traffic["pool"]):
        rng = np.random.default_rng([int(seed) & SEED_MASK, j])
        arrays = gen.generate(nthreads, apt, rng, **config["workload"])
        n = len(arrays["ops"])
        faults = [(n * f["at"][0] // f["at"][1], f["kind"], f["blade"])
                  for f in traffic["faults"]]
        jobs.append(Job(name=f"{config['name']}#{j}", faults=faults,
                        **arrays))
    return jobs
