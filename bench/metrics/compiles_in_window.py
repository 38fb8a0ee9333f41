"""Programs JAX compiled, or loaded from its persistent cache, inside the
window (``jax.monitoring`` events).  It should read 0."""


def read(r):
    return r.compiles_in_window
