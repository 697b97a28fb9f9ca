"""Share of the roofline of the streaming assignment kernel (both passes
of every iteration): the least time the algorithm's needed work over the
active support rows takes on the chip, over the kernel's device time."""
from benchlib.readers import assign_roofline


def read(ctx):
    return assign_roofline(ctx)
