"""Device: backend compilations (a persistent-cache hit included) that
ended inside the window, from JAX's ``jax.monitoring`` events.  Set-up
warms every shape the cell uses, so this should read 0."""


def read(run):
    return run.compiles
