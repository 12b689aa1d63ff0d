"""The control of the comparison that decides ``correct``, at a cell's size.

The control is the plain reference put in the program's place with its
modeled per-thread clocks in float32, the precision below the float64
that the configurations state.  For each seed this draws the cell's
pool as a run does, replays the sampled pool entries through the
reference in both precisions, and prints the numbers ``check.py``
compares, beside their limits: the control has to fail at least one.

Usage: python3 benchmarks/chip/control.py --workload <cell> --seeds <n> ...
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import sys
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, run  # noqa: E402
from benchmarks.chip.pool import make_pool  # noqa: E402


def control_numbers(cell: str, seed: int) -> dict:
    import numpy as np

    _, _, config, traffic = run.load_cell(cell)
    pool = make_pool(config, traffic, seed)
    rack_kw = dict(config["rack"])
    per_job = []
    for p in run.reference_sample(traffic, pool, seed):
        ref = check.digest(run.reference_job(pool[p], rack_kw))
        ctl = check.digest(run.reference_job(pool[p], rack_kw,
                                             clock_dtype=np.float32))
        per_job.append(check.compare(ctl, ref))
    numbers = check.combine(per_job)
    return {"cell": cell, "seed": seed, "numbers": numbers,
            "correct": check.verdict(numbers)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--workers", type=int, default=1)
    args = ap.parse_args(argv)
    tasks = [(c, s) for c in args.workload for s in args.seeds]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(args.workers, mp_context=ctx) as ex:
        futures = [ex.submit(control_numbers, c, s) for c, s in tasks]
        for f in futures:
            print(json.dumps(f.result()), flush=True)
    print(json.dumps({"limits": check.LIMITS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
