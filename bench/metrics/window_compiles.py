"""Programs lowered inside the window (jit cache misses, persistent-cache
hits included), from jax.monitoring. Should be 0."""


def read(run):
    return run.window_compiles
