"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader returns None where it finds nothing to read (no trace, no
Pallas kernel on the path), and the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

from benchlib import work

# Device op names of the streaming Pallas kernels, as the profiler names
# them on the TPU: the custom call carries the name of the jitted wrapper
# (``%streaming_assign_pallas``, ``%streaming_assign_pallas.1``, from
# kernels/fused_step.py).
PALLAS_MARKERS = ("streaming_assign_pallas",)


def is_pallas(op_name: str) -> bool:
    return any(m in op_name for m in PALLAS_MARKERS)


def pallas_s(summary) -> float:
    return sum(s for n, s in summary.op_s.items() if is_pallas(n))


def other_s(summary) -> float:
    return sum(s for n, s in summary.op_s.items() if not is_pallas(n))


def idle_share(ctx):
    if ctx.summary is None:
        return None
    return 100.0 * ctx.summary.idle_share


def per_step_ms(ctx, seconds_of):
    if ctx.summary is None or not ctx.counters.get("steps"):
        return None
    return 1e3 * seconds_of(ctx.summary) / ctx.counters["steps"]


def assign_roofline(ctx):
    """Share of the roofline of the two streaming passes of every fit
    iteration in the window, over the Pallas kernels' device time."""
    if ctx.summary is None or ctx.peaks is None:
        return None
    t = pallas_s(ctx.summary)
    if t <= 0:
        return None
    c = ctx.config
    b, d = c["batch_size"], c["d"]
    w = c["batch_size"] + c["tau"]
    flops = nbytes = 0.0
    for iters, counts in zip(ctx.counters["iters"],
                             ctx.counters["center_counts"]):
        f, y = work.fit_work(iters, counts, b, w, d)
        flops += f
        nbytes += y
    return work.roofline_share(flops, nbytes, t, ctx.peaks)[0]


def counter(ctx, name: str):
    v = ctx.counters.get(name)
    return None if v is None else float(v)
