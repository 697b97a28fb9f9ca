"""Share of the measured window in which no operation ran on the device:
1 - (union of device op intervals) / window, from the profiler trace."""
from benchlib.readers import idle_share


def read(ctx):
    return idle_share(ctx)
