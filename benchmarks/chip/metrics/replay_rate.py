"""Accesses of the jobs the window completed, over the time from the
window's start to the end of its last job (host clock)."""


def read(m):
    if m.window_s <= 0:
        return None
    return sum(j["accesses"] for j in m.jobs) / m.window_s
