"""Device milliseconds of the Gram-row kernel (``cached_assign_dots_pallas``:
both index-data passes, and the recompute where it runs one pass of the
whole Gram) per fit iteration in the window, from the profiler trace."""
from benchlib.gram_work import kernel_s, per_step_ms


def read(ctx):
    return per_step_ms(ctx, kernel_s)
