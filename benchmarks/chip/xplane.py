"""Reduction of a JAX profiler trace to device time, busy share and idle
gaps.

A trace is read into plain tuples, ``[(plane, [(line, [(name, start_ns,
dur_ns), ...]), ...]), ...]``, so that every reduction below runs on a
small synthetic list as well as on a recorded ``.xplane.pb``.  Device
planes are those named ``/device:<platform>:<n>`` (``/device:TPU:0``);
whole executables are on the line named :data:`MODULES_LINE`.  The
per-operation line is left unread: the wave loop's while body puts
about 30 operation events on it per access, millions per job, while an
executable's interval already bounds the device's busy time.
"""

from __future__ import annotations

import glob
import os
import re

MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:[A-Z_]+:\d+$")


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%while.28 = (...)``
    becomes ``%while.28``; executables (``jit_f(3)``) keep theirs."""
    return name.split(" = ", 1)[0]


def read(path: str, host_prefix: str) -> list:
    """The parts of one ``.xplane.pb`` file the reductions use, as plain
    tuples: the executable line of each device plane, and the host spans
    whose name starts with ``host_prefix``."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        if _DEVICE.match(p.name):
            lines = [(ln.name, [(short_name(e.name), e.start_ns,
                                 e.duration_ns) for e in ln.events])
                     for ln in p.lines if ln.name == MODULES_LINE]
        else:
            lines = [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                for e in ln.events
                                if e.name.startswith(host_prefix)])
                     for ln in p.lines]
        planes.append((p.name, lines))
    return planes


def find(log_dir: str) -> str:
    """The one trace file the profiler wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def device_planes(planes: list) -> list:
    return [p for p in planes if _DEVICE.match(p[0])]


def line_events(plane, line: str) -> list:
    """Events of the named line of one plane, as (name, start, end)."""
    return [(n, s, s + d) for ln, evs in plane[1] if ln == line
            for n, s, d in evs]


def host_spans(planes: list, prefix: str) -> list:
    """Host-side spans (TraceAnnotations) whose name starts with
    ``prefix``, as (name, start, end), from every non-device plane."""
    return [(n, s, s + d) for p in planes if not _DEVICE.match(p[0])
            for _, evs in p[1] for n, s, d in evs if n.startswith(prefix)]


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(merged, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def busy_ns(merged, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which an operation ran."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged, lo: float, hi: float) -> list:
    """Idle intervals of [lo, hi) between the merged busy intervals."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def time_by_name(events, k: int = 10) -> list:
    """The ``k`` names with the most total time, as [name, seconds]."""
    tot: dict[str, float] = {}
    for n, s, e in events:
        tot[n] = tot.get(n, 0.0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns * 1e-9] for n, ns in top]


def label_gaps(idle, spans, k: int = 10) -> list:
    """Idle time by what the host was doing: each gap's time is split
    over the host spans that overlap it, the innermost (shortest) span
    taking each stretch, and the rest is labelled ``(no span)``.
    Returns the ``k`` labels with the most idle time, as [label, s]."""
    tot: dict[str, float] = {}
    by_len = sorted(spans, key=lambda sp: sp[2] - sp[1])
    for gs, ge in idle:
        pieces = [(gs, ge)]
        for name, s, e in by_len:
            rest = []
            for ps, pe in pieces:
                a, b = max(ps, s), min(pe, e)
                if a >= b:
                    rest.append((ps, pe))
                    continue
                tot[name] = tot.get(name, 0.0) + (b - a)
                if ps < a:
                    rest.append((ps, a))
                if b < pe:
                    rest.append((b, pe))
            pieces = rest
            if not pieces:
                break
        left = sum(pe - ps for ps, pe in pieces)
        if left:
            tot["(no span)"] = tot.get("(no span)", 0.0) + left
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns * 1e-9] for n, ns in top]


def seconds_named(events, fragment: str, lo: float, hi: float) -> float:
    """Total time, clipped to [lo, hi), of the events whose name holds
    ``fragment`` (executables are named ``jit_<function>(<id>)``)."""
    return sum(min(e, hi) - max(s, lo) for n, s, e in events
               if fragment in n and e > lo and s < hi) * 1e-9

