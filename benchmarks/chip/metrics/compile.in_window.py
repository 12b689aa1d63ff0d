"""Programs compiled or loaded from the cache inside the window; a warm
window reads 0 (JAX monitoring events)."""


def read(m):
    return m.compiles_window
