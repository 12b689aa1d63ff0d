"""The comparison that decides ``correct``.

Each job the window completes is compared with the plain reference
(``reference/``, the scalar MIND rack emulator) replaying the same
trace and faults on a fresh rack.  The numbers compared, each with its
limit (PERF.md gives the readings each limit was set from):

* ``stat_mismatches``: coherence counters (every ``EpochStats`` field:
  hits, fetches, invalidations, flushes, evictions, protection faults,
  splits, merges) that differ from the reference, summed over jobs.
  Exact: limit 0.
* ``epoch_mismatches``: Bounded Splitting epoch reports and directory
  timeline entries that differ, summed over jobs.  Exact: limit 0.
* ``fault_mismatches``: fault-plane report fields that differ, summed
  over jobs.  Exact: limit 0.
* ``tcam_mismatches``: answers of the switch's TCAM stages that differ
  from the reference's tables, summed over accesses and jobs: per
  access, the address each kernel was asked about, protection's
  verdict, translation's blade and the translation row that served it
  (its base, prefix, blade and offset).  Exact: limit 0.
* ``time_gap``: the widest relative gap of the modeled time
  (``runtime_us``, ``total_thread_us`` and each latency-breakdown
  component) over jobs.  Sound runs read at most ~7e-12 (float64 sums
  taken in another order); the control, float32 clocks, reads 1.6e-6
  and more.  The limit sits between them, nearer the control.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

LIMITS = {
    "stat_mismatches": 0,
    "epoch_mismatches": 0,
    "fault_mismatches": 0,
    "tcam_mismatches": 0,
    "time_gap": 1e-8,
}


def digest(res) -> dict:
    """What a replay answered, as plain numbers (program or reference)."""
    times = {"runtime_us": res.runtime_us,
             "total_thread_us": res.total_thread_us}
    times.update({f"breakdown.{k}": v
                  for k, v in res.latency_breakdown_us.items()})
    epochs = [dataclasses.asdict(e) for e in res.epoch_reports]
    epochs += [{"directory_entries_after": v}
               for v in res.directory_timeline]
    return {"stats": dataclasses.asdict(res.stats), "times": times,
            "epochs": epochs,
            "faults": [dataclasses.asdict(f) for f in res.fault_reports]}


def _mismatches(got: list[dict], ref: list[dict]) -> int:
    """Fields of the reference's entries that differ, with a missing or
    extra entry counted as one field each."""
    return (sum(g.get(k) != v for g, r in zip(got, ref) for k, v in r.items())
            + abs(len(got) - len(ref)))


def _differ(got, ref) -> int:
    """Entries of ``ref`` that ``got`` differs from, rows of a 2-D array
    counted once; a missing or extra entry counts as one."""
    n = min(len(got), len(ref))
    neq = np.asarray(got[:n]) != np.asarray(ref[:n])
    if neq.ndim > 1:
        neq = neq.any(axis=1)
    return int(neq.sum()) + abs(len(got) - len(ref))


def _joined(calls: list, key: str) -> np.ndarray:
    return (np.concatenate([np.asarray(c[key]) for c in calls])
            if calls else np.zeros(0))


def _served(calls: list) -> np.ndarray:
    """Per access, the translation row that a call's answer points at,
    or -1s where it points at none."""
    out = []
    for c in calls:
        table = np.asarray(c["table"], np.int64).reshape(-1, 4)
        rows = np.asarray(c["rows"], np.int64)
        hit = (rows >= 0) & (rows < len(table))
        row = np.full((len(rows), 4), -1, np.int64)
        row[hit] = table[rows[hit]]
        out.append(row)
    return np.concatenate(out) if out else np.zeros((0, 4), np.int64)


def _tcam_mismatches(got: dict | None, ref: dict) -> int:
    """The program's TCAM answers (``run.TcamTap``, calls in order)
    against the reference's per access (``reference/tcam.py``)."""
    got = got or {"protect_check": [], "translate_lookup": []}
    prot, tr = got["protect_check"], got["translate_lookup"]
    return (_differ(_joined(prot, "vaddrs"), ref["vaddrs"])
            + _differ(_joined(prot, "allow").astype(bool), ref["allow"])
            + _differ(_joined(tr, "vaddrs"), ref["vaddrs"])
            + _differ(_joined(tr, "blade"), ref["rows"][:, 2])
            + _differ(_served(tr), ref["rows"]))


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared for one job (``got``) against its reference."""
    # The reference's fields are what is checked: a counter the program
    # adds later is not the reference's to judge.
    return {
        "stat_mismatches": sum(got["stats"].get(k) != v
                               for k, v in ref["stats"].items()),
        "epoch_mismatches": _mismatches(got["epochs"], ref["epochs"]),
        "fault_mismatches": _mismatches(got["faults"], ref["faults"]),
        "tcam_mismatches": (_tcam_mismatches(got.get("tcam"), ref["tcam"])
                            if "tcam" in ref else 0),
        "time_gap": max((_rel(got["times"].get(k, math.nan), v)
                         for k, v in ref["times"].items()), default=0.0),
    }


def differences(got: dict, ref: dict, limit: int = 6) -> list[str]:
    """The first counters and times in which ``got`` differs from the
    reference, each with both values, for the run's error output."""
    out = [f"{k} {got['stats'].get(k)} vs {v}"
           for k, v in ref["stats"].items() if got["stats"].get(k) != v]
    out += [f"{k} {got['times'].get(k)!r} vs {v!r}"
            for k, v in ref["times"].items()
            if _rel(got["times"].get(k, math.nan), v) > LIMITS["time_gap"]]
    return out[:limit]


def combine(per_job: list[dict]) -> dict:
    """Counts add up over jobs; the gap is the widest of them."""
    out = {k: 0 for k in LIMITS}
    out["time_gap"] = 0.0
    for c in per_job:
        for k, v in c.items():
            out[k] = max(out[k], v) if k == "time_gap" else out[k] + v
    return out


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
