"""Device milliseconds of the streaming Pallas kernels per fit iteration
in the window, from the profiler trace."""
from benchlib.readers import pallas_s, per_step_ms


def read(ctx):
    return per_step_ms(ctx, pallas_s)
