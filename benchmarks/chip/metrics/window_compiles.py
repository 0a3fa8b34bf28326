"""Backend compiles that jax.monitoring reported inside the window."""


def read(w):
    return w.compiles
