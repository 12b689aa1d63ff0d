"""Process start to window open: imports, compile-cache loads, the
pool's generation and one warm replay of every pool job (host clock)."""


def read(m):
    return m.setup_s
