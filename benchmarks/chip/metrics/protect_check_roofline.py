"""The protection TCAM kernel's share of its roofline: the least time
its calls' bytes take at the chip's HBM bandwidth (``tcam_cost``,
``peaks.json``), over the kernel's device time in the trace."""

from benchmarks.chip import peaks, tcam_cost, xplane

KERNEL = "protect_check"
EXECUTABLE = "_protect_call"


def read(m):
    t = m.traced
    if not t:
        return None
    calls = [(n, r) for k, n, r in t["tcam_calls"] if k == KERNEL]
    secs = xplane.seconds_named(t["modules"], EXECUTABLE, t["lo_ns"],
                                t["hi_ns"])
    if not calls or secs <= 0:
        return None
    least = sum(tcam_cost.KERNELS[KERNEL](n, r)["bytes"] for n, r in calls) \
        / peaks.peak(m.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / secs
