"""Chip benchmark of the batched MIND replay: one run of one cell.

Usage (from the root of a checkout, on a machine with a TPU):

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration file gives the rack and the trace generator, its traffic
file (``traffic/<traffic>.json``) the job pool, and each metric is read
by ``metrics/<metric>.py``; nothing here names a cell.

Each job is one ``DisaggregatedRack(engine="batched", **rack).run(trace)``
on a fresh rack, the program's normal path.  Set-up loads the compile
cache, draws the pool from ``--seed`` and replays every pool job once,
so that every shape the window uses is compiled or loaded before it
opens.  The window is a closed loop: jobs run back to back, cycling the
pool, until ``--seconds`` have passed, and the job in progress is
finished.  After the window the device's peak memory is read, the
program's state is dropped, and every job of the pool entries sampled
from the seed is compared with the plain reference (``check.py``,
``reference/``): its statistics, epochs, fault reports and modeled
times, and its TCAM kernels' answers for every access.

With ``--trace 1`` the profiler records the first jobs of the window
(``traced_jobs`` in the traffic file, default 1) and the per-layer
metrics are printed instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``: each number compared with its
limit); the same checks end standard error.  The run refuses, with a
nonzero exit and no result, any device that is not a TPU, fewer chips
than the cell asks for, and a checkout without the program.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check  # noqa: E402
from benchmarks.chip.pool import SEED_MASK, make_pool  # noqa: E402


class Refused(Exception):
    """The run cannot measure here: it prints no result."""


def load_cell(name: str):
    """The cell, its configuration and its traffic, found by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    """The cell's metrics of the kind the run reports."""
    return [m for m in spec["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, m) -> float | None:
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(m)


def require_device(chips: int):
    """The devices the cell runs on; a TPU with enough chips or nothing."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, but JAX's first device is "
                      f"{devs[0].platform!r} ({devs[0].device_kind}); "
                      "refusing to fall back")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compile cache, at a fixed path in the checkout
    unless the environment names one.  The TPU runtime's own log files
    would go to a fixed path outside the checkout: they are off."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import enable_compile_cache

    return enable_compile_cache()


def program_job(job, rack_kw: dict):
    """One job through the program's normal batched path."""
    from repro.core import faults as flt
    from repro.core.emulator import DisaggregatedRack
    from repro.core.traces import Trace

    rack = DisaggregatedRack(engine="batched", **rack_kw)
    if job.faults:
        rack.schedule_fault_plan([flt.FaultEvent(i, k, b)
                                  for i, k, b in job.faults])
    return rack.run(Trace(job.name, job.threads, job.ops, job.offsets,
                          job.arena_bytes, job.shared_bytes))


def reference_job(job, rack_kw: dict, clock_dtype=None):
    """The same job through the plain reference."""
    import numpy as np

    from benchmarks.chip.reference import faults as rflt
    from benchmarks.chip.reference.emulator import DisaggregatedRack

    rack = DisaggregatedRack(clock_dtype=clock_dtype or np.float64, **rack_kw)
    if job.faults:
        rack.schedule_fault_plan([rflt.FaultEvent(i, k, b)
                                  for i, k, b in job.faults])
    return rack.run(job)


def reference_tcam(job, rack_kw: dict) -> dict:
    """The reference's TCAM answers for the job's accesses."""
    from benchmarks.chip.reference import tcam
    from benchmarks.chip.reference.emulator import DisaggregatedRack

    return tcam.expected(DisaggregatedRack(**rack_kw), job)


class TcamTap:
    """The program's calls of its two TCAM kernels during one job: each
    call's shape, and with ``keep`` its addresses, tables and answers,
    which ``check.py`` holds against the reference's."""

    NAMES = ("protect_check", "translate_lookup")

    def __init__(self, keep: bool):
        self.keep = keep
        self.shapes: list = []
        self.calls: dict = {k: [] for k in self.NAMES}

    @contextlib.contextmanager
    def attached(self):
        from repro.kernels import ops

        orig = {k: getattr(ops, k) for k in self.NAMES}

        def protect(pdids, vaddrs, need, table, **kw):
            allow = orig["protect_check"](pdids, vaddrs, need, table, **kw)
            self._add("protect_check", vaddrs, table, allow=allow)
            return allow

        def translate(vaddrs, table, **kw):
            blade, rows = orig["translate_lookup"](vaddrs, table, **kw)
            self._add("translate_lookup", vaddrs, table, blade=blade,
                      rows=rows)
            return blade, rows

        ops.protect_check, ops.translate_lookup = protect, translate
        try:
            yield self
        finally:
            for k, fn in orig.items():
                setattr(ops, k, fn)

    def _add(self, name, vaddrs, table, **answers):
        self.shapes.append((name, len(vaddrs), len(table)))
        if self.keep:
            self.calls[name].append(dict(vaddrs=vaddrs, table=table,
                                         **answers))


def run_job(job, rack_kw: dict, keep_tcam: bool = False) -> dict:
    """Time one job; a job that raises, leaves the batched engine or
    replays fewer accesses than it was given has failed.  With
    ``keep_tcam`` the TCAM kernels' answers go into its digest."""
    tap = TcamTap(keep_tcam)
    t0 = time.perf_counter()
    try:
        with tap.attached():
            res = program_job(job, rack_kw)
    except Exception as e:  # a failed job is counted, not fatal
        return {"wall_s": time.perf_counter() - t0, "ok": False,
                "error": f"{type(e).__name__}: {e}", "accesses": 0,
                "phases": {}, "digest": None, "tcam_shapes": tap.shapes}
    wall = time.perf_counter() - t0
    ok = res.engine == "batched" and res.stats.accesses == len(job)
    dig = check.digest(res)
    if keep_tcam:
        dig["tcam"] = tap.calls
    return {"wall_s": wall, "ok": ok, "accesses": len(job) if ok else 0,
            "error": None if ok else (f"engine {res.engine}, "
                                      f"{res.stats.accesses} of {len(job)} "
                                      "accesses"),
            "phases": dict(res.phase_times), "digest": dig,
            "tcam_shapes": tap.shapes}


class Recorder:
    """While tracing: engine phase spans (the program's ``phase_times``
    ticks, on the host clock)."""

    def __init__(self):
        self.spans: list = []

    @contextlib.contextmanager
    def attached(self):
        from repro.dataplane.engine import BatchedDataPlane

        orig_tick = BatchedDataPlane._tick
        spans = self.spans

        def tick(plane, key, t0):
            t1 = orig_tick(plane, key, t0)
            spans.append((key, t0, t1))
            return t1

        BatchedDataPlane._tick = tick
        try:
            yield
        finally:
            BatchedDataPlane._tick = orig_tick


def reduce_trace(log_dir: str, rec: Recorder, anchors: list) -> dict:
    """Device time, busy share and idle gaps of the traced jobs.

    ``anchors`` holds, per traced job, its TraceAnnotation name and the
    host clock at which it was entered; the annotation's start in the
    trace puts the phase spans on the trace's clock."""
    from benchmarks.chip import xplane

    planes = xplane.read(xplane.find(log_dir), "job ")
    jobs = {n: (s, e) for n, s, e in xplane.host_spans(planes, "job ")}
    if not jobs:
        raise RuntimeError("the trace holds no job spans")
    lo = min(s for s, _ in jobs.values())
    hi = max(e for _, e in jobs.values())
    offsets = [jobs[n][0] - pc * 1e9 for n, pc in anchors if n in jobs]
    off = sum(offsets) / len(offsets)
    spans = [(f"job:{k}", s * 1e9 + off, e * 1e9 + off)
             for k, s, e in rec.spans]
    spans += [(n, s, e) for n, (s, e) in jobs.items()]
    devs = xplane.device_planes(planes)
    if not devs:
        raise RuntimeError("the trace holds no device plane")
    per_dev = [xplane.line_events(p, xplane.MODULES_LINE) for p in devs]
    merged = [xplane.merge((s, e) for _, s, e in ev) for ev in per_dev]
    busy = sum(xplane.busy_ns(m, lo, hi) for m in merged) / len(merged)
    modules = [ev for dev in per_dev for ev in dev]
    return {"lo_ns": lo, "hi_ns": hi, "window_s": (hi - lo) * 1e-9,
            "busy_s": busy * 1e-9, "modules": modules,
            "device_ops": xplane.time_by_name(
                [(n, s, e) for n, s, e in modules if e > lo and s < hi]),
            "idle_gaps": xplane.label_gaps(xplane.gaps(merged[0], lo, hi),
                                           spans)}


class GcPauses:
    """Full (oldest-generation) collections and their time, while open."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self):
        gc.callbacks.remove(self._on)


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def window(pool, rack_kw, seconds, traced_jobs, log_dir, sample):
    """The measured window; with ``log_dir`` its first ``traced_jobs``
    jobs run under the profiler, each in a span of its own.  Jobs of the
    pool entries in ``sample`` keep their TCAM answers."""
    import jax

    rec = Recorder()
    jobs, anchors = [], []

    def one():
        i = len(jobs)
        r = run_job(pool[i % len(pool)], rack_kw,
                    keep_tcam=i % len(pool) in sample)
        r.update(pool_index=i % len(pool), traced=bool(log_dir) and
                 i < traced_jobs)
        jobs.append(r)

    with contextlib.ExitStack() as stack:
        if log_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
            stack.enter_context(rec.attached())
        t_open = time.perf_counter()
        for i in range(traced_jobs if log_dir else 0):
            anchors.append((f"job {i}", time.perf_counter()))
            with jax.profiler.TraceAnnotation(f"job {i}"):
                one()
    t_end = time.perf_counter()
    while t_end - t_open < seconds:
        one()
        t_end = time.perf_counter()
    return jobs, t_end - t_open, rec, anchors


def reference_sample(traffic: dict, pool: list, seed: int) -> list:
    """The pool entries compared with the reference, drawn from the seed."""
    import numpy as np

    rng = np.random.default_rng([seed & SEED_MASK, 1 << 32])
    n = min(traffic.get("reference_jobs", len(pool)), len(pool))
    return sorted(rng.choice(len(pool), size=n, replace=False).tolist())


def correctness(pool, jobs, warm, rack_kw, sample) -> dict:
    """Every job of the sampled pool entries, warm-up and window,
    against the reference's replay of that entry."""
    per_job = []
    for p in sample:
        ref = check.digest(reference_job(pool[p], rack_kw))
        ref["tcam"] = reference_tcam(pool[p], rack_kw)
        for r in warm[p:p + 1] + [j for j in jobs if j["pool_index"] == p]:
            if r["digest"] is not None:
                per_job.append(check.compare(r["digest"], ref))
                diff = check.differences(r["digest"], ref)
                if diff:
                    print(f"pool entry {p} differs: {'; '.join(diff)}",
                          file=sys.stderr)
    return check.combine(per_job)


def report_checks(numbers: dict) -> dict:
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in check.LIMITS.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return measure(args)
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1


def measure(args) -> int:
    spec, cell, config, traffic = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program sources under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    enable_cache()
    devices = require_device(cell["chips"])
    dev = devices[0]

    from benchmarks.chip.compile_log import CompileLog

    compiles = CompileLog()
    rack_kw = dict(config["rack"])
    pool = make_pool(config, traffic, args.seed)
    sample = reference_sample(traffic, pool, args.seed)
    warm = [run_job(job, rack_kw, keep_tcam=i in sample)
            for i, job in enumerate(pool)]
    for i, r in enumerate(warm):
        r["pool_index"] = i
    c_setup = compiles.snapshot()
    setup_s = time.perf_counter() - T0

    log_dir = None
    if args.trace:
        log_dir = str(ROOT / ".bench_trace" / args.workload)
        shutil.rmtree(log_dir, ignore_errors=True)
    traced_jobs = traffic.get("traced_jobs", 1)
    # The set-up's long-lived objects (JAX's, the pool's) are moved out
    # of the collector's reach, so that the window's collections scan
    # what the jobs make and not what the harness holds.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    jobs, window_s, rec, anchors = window(pool, rack_kw, args.seconds,
                                          traced_jobs, log_dir, set(sample))
    pauses.close()
    gc.unfreeze()
    c_window = compiles.snapshot()
    mem_peak = peak_memory(devices)
    n_ok = sum(j["ok"] for j in jobs)
    print(f"window: {len(jobs)} jobs ({n_ok} ok) in {window_s:.3f} s; "
          f"set-up {setup_s:.3f} s; compiles in set-up {c_setup[0]} "
          f"({c_setup[2]} cache hits, {c_setup[1]:.3f} s), in window "
          f"{c_window[0] - c_setup[0]}; compiled, not from the cache: "
          f"{compiles.missed}")
    walls = sorted(j["wall_s"] for j in jobs)
    print(f"job wall s: min {walls[0]:.4f} median "
          f"{walls[len(walls) // 2]:.4f} max {walls[-1]:.4f}; full "
          f"collections in the window: {pauses.count} taking "
          f"{pauses.seconds:.3f} s")
    for j in jobs:
        if j["error"]:
            print(f"failed job {j['pool_index']}: {j['error']}",
                  file=sys.stderr)

    traced = None
    if log_dir:
        try:
            traced = reduce_trace(log_dir, rec, anchors)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        tj = [j for j in jobs if j["traced"]]
        traced.update(
            jobs=tj, accesses=sum(j["accesses"] for j in tj),
            phases={k: sum(j["phases"].get(k, 0.0) for j in tj)
                    for k in tj[0]["phases"]} if tj else {},
            tcam_calls=[c for j in tj for c in j["tcam_shapes"]],
            host_window_s=sum(j["wall_s"] for j in tj))

    m = SimpleNamespace(
        setup_s=setup_s, window_s=window_s, jobs=jobs, traced=traced,
        compiles_setup=c_setup[0], compiles_window=c_window[0] - c_setup[0],
        device_kind=dev.device_kind)
    metrics = {}
    for spec_m in metrics_for(spec, args.workload, bool(args.trace)):
        v = read_metric(spec_m["name"], m)
        if v is not None:
            metrics[spec_m["name"]] = {"value": v, "unit": spec_m["unit"]}

    # The program's state goes before the reference runs.
    for j in jobs:
        j.pop("phases", None)
    gc.collect()
    t_ref = time.perf_counter()
    numbers = correctness(pool, jobs, warm, rack_kw, sample)
    print(f"reference: pool entries {sample} in "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    failed = sum(not j["ok"] for j in jobs)
    correct = check.verdict(numbers) and failed == 0 and bool(jobs)
    checks = report_checks(numbers)

    out = {"correct": correct, "attempted": len(jobs), "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": mem_peak}}
    if traced is not None:
        out["device"].update(busy_s=traced["busy_s"],
                             window_s=traced["window_s"])
        out["breakdown"] = {"device_ops": traced["device_ops"],
                            "idle_gaps": traced["idle_gaps"]}
    out["checks"] = checks
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
