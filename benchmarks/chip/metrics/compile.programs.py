"""Programs compiled or loaded from the persistent cache during set-up
(JAX monitoring events)."""


def read(m):
    return m.compiles_setup
