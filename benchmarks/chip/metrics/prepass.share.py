"""Share of the traced window the host pre-passes took: directory
residency and blade-cache occupancy (``phase_times``, host clock)."""

PHASES = ("residency_prepass", "cache_prepass")


def read(m):
    t = m.traced
    if not t or t["host_window_s"] <= 0 or not set(PHASES) & set(t["phases"]):
        return None
    return 100.0 * sum(t["phases"].get(k, 0.0) for k in PHASES) \
        / t["host_window_s"]
