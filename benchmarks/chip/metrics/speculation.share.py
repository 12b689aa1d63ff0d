"""Share of the traced window spent on discarded speculative epoch
chunks and their snapshots (``phase_times``, host clock)."""

PHASES = ("speculation_overhead",)


def read(m):
    t = m.traced
    if not t or t["host_window_s"] <= 0 or not set(PHASES) & set(t["phases"]):
        return None
    return 100.0 * sum(t["phases"].get(k, 0.0) for k in PHASES) \
        / t["host_window_s"]
