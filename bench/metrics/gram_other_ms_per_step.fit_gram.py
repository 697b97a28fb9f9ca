"""Device milliseconds of every other operation (the Gram-row gather, the
V scatter, the ring append, the recompute's gathers, k-means++) per fit
iteration in the window, from the profiler trace."""
from benchlib.gram_work import other_s, per_step_ms


def read(ctx):
    return per_step_ms(ctx, other_s)
