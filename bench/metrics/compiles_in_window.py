"""Programs lowered during the window (``jax.monitoring`` lowering
events), compiled or loaded from the cache: a shape the warm-up missed."""
UNIT = "count"


def read(run):
    return run.compiles
