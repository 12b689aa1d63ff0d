"""Device time of the fused MSI wave loop (the ``_lane_replay``
executable) per access of the traced jobs (profiler trace)."""

from benchmarks.chip import xplane

EXECUTABLE = "_lane_replay"


def read(m):
    t = m.traced
    if not t or not t["accesses"]:
        return None
    secs = xplane.seconds_named(t["modules"], EXECUTABLE, t["lo_ns"],
                                t["hi_ns"])
    return secs * 1e9 / t["accesses"] if secs > 0 else None
