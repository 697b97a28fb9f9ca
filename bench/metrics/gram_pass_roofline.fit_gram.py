"""Share of the roofline of the Gram-row kernel: the least time the work
its passes need over the active support slots takes on the chip
(``benchlib.gram_work``), over the kernel's device time."""
from benchlib.gram_work import roofline


def read(ctx):
    return roofline(ctx)
