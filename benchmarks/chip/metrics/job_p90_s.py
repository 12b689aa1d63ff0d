"""The 90th percentile of the wall time of every job the window
completed (host clock; ``statistics.quantiles``, inclusive)."""

import statistics


def read(m):
    times = [j["wall_s"] for j in m.jobs]
    if len(times) < 2:
        return times[0] if times else None
    return statistics.quantiles(times, n=10, method="inclusive")[-1]
