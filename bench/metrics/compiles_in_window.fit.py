"""Programs traced, lowered, compiled or read from the persistent cache
inside the measured window (``jax.monitoring`` compile events)."""
from benchlib.readers import counter


def read(ctx):
    return counter(ctx, "compiles_in_window")
