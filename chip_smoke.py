"""Chip smoke test: the batched MIND replay on one TPU chip.

Drives the switch data plane's batched replay engine through its normal
entry point, ``DisaggregatedRack(engine="batched").run``, on two
deployments, in one process:

* Phase A, headline: 8 compute blades x 10 threads replay YCSB-A (50/50
  reads/updates, zipfian over a 24 MB fully shared store) at 20,000
  accesses per thread (1.6M), with the paper's defaults (30k directory
  slots, 512 MB blade caches, Bounded Splitting on) and 100 ms epochs.
* Phase B, directory pressure: a TensorFlow-like trace on 8 blades x 4
  threads, 24 MB private per thread and 8 MB shared, 20,000 accesses per
  thread.  That maps ~49k initial 16 KB regions against the 30k slots,
  so capacity-eviction packets replay on the device.

Each phase first checks that the TCAM kernels lower to Mosaic
(``tpu_custom_call``), replays its whole trace batched, and then replays
a prefix on a fresh scalar rack (the reference emulator) and a fresh
batched rack: the coherence stats must be identical and the modeled
runtime equal to a relative 1e-9.  Any failure raises, and the exit code
is nonzero.

The script runs only on a TPU.  It never degrades to the scalar engine
or to interpreted kernels, and it exits nonzero, printing no result,
when JAX's first device is not a TPU or the repository's sources are not
next to it.  The JAX compile cache goes where
``repro.compile_cache.enable_compile_cache`` puts it.

Usage: python chip_smoke.py

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

PREFIX_A = 150_000
PREFIX_B = 100_000


class CompileLog:
    """Counts XLA compiles (and their seconds) through JAX's monitoring
    events; a persistent-cache hit is counted as a compile too, since it
    still passes through the backend-compile span."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


def check_mosaic(batch: int) -> None:
    """The TCAM programs the engine will run are Mosaic kernels."""
    from repro.kernels import ops as K

    for name, lowered in K.lower_tcam(batch, 1).items():
        if "tpu_custom_call" not in lowered.as_text():
            raise RuntimeError(f"{name} did not lower to a Mosaic kernel")


def run_phase(name: str, rack_kw: dict, trace, prefix: int,
              compiles: CompileLog) -> None:
    from benchmarks.dataplane_bench import STAT_FIELDS
    from repro.core.emulator import DisaggregatedRack

    n = len(trace)
    check_mosaic(n)

    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    res = DisaggregatedRack(system="mind", engine="batched",
                            **rack_kw).run(trace)
    wall = time.perf_counter() - t0
    c1 = compiles.snapshot()
    if res.engine != "batched":
        raise RuntimeError(f"phase {name} ran on the {res.engine} engine")
    if res.stats.accesses != n:
        raise RuntimeError(f"phase {name} replayed {res.stats.accesses} "
                           f"of {n} accesses")
    print(f"phase {name}: {n} accesses batched in {wall:.3f} s "
          f"(first reading, compiles included: {n / wall:.0f} accesses/s); "
          f"{c1[0] - c0[0]} compiles, {c1[1] - c0[1]:.3f} s compiling, "
          f"{c1[2] - c0[2]} cache hits")
    print(f"phase {name} phase_times: "
          + json.dumps({k: round(v, 6) for k, v in res.phase_times.items()}))

    t0 = time.perf_counter()
    ref = DisaggregatedRack(system="mind", engine="scalar",
                            **rack_kw).run(trace, max_accesses=prefix)
    t_ref = time.perf_counter() - t0
    got = DisaggregatedRack(system="mind", engine="batched",
                            **rack_kw).run(trace, max_accesses=prefix)
    diverged = {f: (getattr(ref.stats, f), getattr(got.stats, f))
                for f in STAT_FIELDS
                if getattr(ref.stats, f) != getattr(got.stats, f)}
    if not math.isclose(got.runtime_us, ref.runtime_us, rel_tol=1e-9):
        diverged["runtime_us"] = (ref.runtime_us, got.runtime_us)
    if got.engine != "batched" or diverged:
        raise RuntimeError(f"phase {name} prefix parity failed over {prefix} "
                           f"accesses (scalar, batched): {diverged}")
    print(f"phase {name}: prefix parity held over {prefix} accesses "
          f"(scalar reference {t_ref:.3f} s); runtime_us {got.runtime_us}")


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources at {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); refusing to fall back",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}")
    compiles = CompileLog()

    from repro.core import traces

    run_phase("A", dict(num_compute_blades=8, threads_per_blade=10,
                        epoch_us=100_000.0),
              traces.ma_trace(80, accesses_per_thread=20_000), PREFIX_A,
              compiles)
    run_phase("B", dict(num_compute_blades=8, threads_per_blade=4),
              traces.tf_trace(32, accesses_per_thread=20_000,
                              private_mb_per_thread=24, shared_mb=8),
              PREFIX_B, compiles)
    n, secs, hits = compiles.snapshot()
    print(f"total: {n} compiles, {secs:.3f} s compiling, {hits} cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
