"""Share of the traced window the engine's host work took: arena and
table set-up, schedule, merge, latency reconstruction and epoch control
(``phase_times``, host clock)."""

PHASES = ("arena_setup", "state_build", "schedule", "merge_writeback",
          "latency_reconstruct", "epoch_control")


def read(m):
    t = m.traced
    if not t or t["host_window_s"] <= 0 or not set(PHASES) & set(t["phases"]):
        return None
    return 100.0 * sum(t["phases"].get(k, 0.0) for k in PHASES) \
        / t["host_window_s"]
