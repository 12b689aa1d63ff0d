"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 minus the union of device-op intervals)."""


def read(m):
    t = m.traced
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
