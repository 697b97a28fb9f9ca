"""Device milliseconds of every other operation (support gathers, the
<C_j, C_j> recompute, the ring append, k-means++ init) per fit iteration
in the window, from the profiler trace."""
from benchlib.readers import other_s, per_step_ms


def read(ctx):
    return per_step_ms(ctx, other_s)
