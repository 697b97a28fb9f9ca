"""Work the index-data passes need, over a Gram's rows, and the readers of
the ``fit_gram`` cells' kernel metrics.

A pass of q batch rows against centers holding a active support slots in
all needs, whatever implements it:

* flops ``2 q a``: one multiply and one add per Gram entry and slot;
* bytes ``4 q min(a, n)``: the batch's Gram entries in the support's
  columns, at most its n columns, in f32, plus ``4 k n``: the dense
  (k, n) window weights V the Gram-row kernel contracts them with.

The ``"gram"`` route of the <C_j, C_j> recompute runs the same kernel
over the whole Gram; it needs ``2 sum_j a_j^2`` flops and
``4 min(sum_j a_j^2, n^2)`` bytes, so the metric's time and its work
cover the same ops.

Active slots before iteration t of T follow ``work.fit_active_rows``'s
even pace (center j at ``min(W, 1 + c_j (t - 1) / T)`` after c_j points
over the fit), capped at the slots the fitted state holds at its end (the
beta rate decays a center that takes a whole batch to exactly 0).

The kernel multiplies f32 at ``Precision.HIGHEST``, which the v5e's MXU
runs as ``HIGHEST_PASSES`` bf16 passes: its flop peak is the bf16 peak
over that.
"""
from __future__ import annotations

from benchlib import work

# bf16 MXU passes of one f32 product at Precision.HIGHEST on a TPU
HIGHEST_PASSES = 6
# device op names of the Gram-row kernel: the custom call carries the name
# of its jitted wrapper (kernels/cached_gather.py)
KERNEL_MARKERS = ("cached_assign_dots_pallas",)


def is_kernel(op_name: str) -> bool:
    return any(m in op_name for m in KERNEL_MARKERS)


def kernel_s(summary) -> float:
    return sum(s for n, s in summary.op_s.items() if is_kernel(n))


def other_s(summary) -> float:
    return sum(s for n, s in summary.op_s.items() if not is_kernel(n))


def active_slots(step: int, iters: int, counts, slots, w: int) -> list:
    """Each center's active slots before iteration ``step`` (1-based) of a
    fit of ``iters`` iterations."""
    frac = (step - 1) / iters
    return [min(w, s, 1.0 + c * frac) for c, s in zip(counts, slots)]


def pass_work(q: int, a: float, n: int, k: int) -> tuple:
    """(flops, bytes) of one pass of q Gram rows against a active slots."""
    return 2.0 * q * a, 4.0 * (q * min(a, n) + k * n)


def recompute_work(a_j, n: int) -> tuple:
    """(flops, bytes) of the recompute's pass over the whole Gram."""
    sq = float(sum(a * a for a in a_j))
    return 2.0 * sq, 4.0 * min(sq, float(n) * n)


def fit_work(iters: int, counts, slots, *, b: int, w: int, n: int,
             route: str) -> tuple:
    """(flops, bytes) the Gram-row kernel needs over one fit: both passes
    of every iteration (against the centers before and after it), and the
    recompute on the centers after it where ``route`` is ``"gram"``."""
    k = len(counts)
    fl = by = 0.0
    for t in range(1, iters + 1):
        before = active_slots(t, iters, counts, slots, w)
        after = active_slots(t + 1, iters, counts, slots, w)
        parts = [pass_work(b, sum(before), n, k),
                 pass_work(b, sum(after), n, k)]
        if route == "gram":
            parts.append(recompute_work(after, n))
        for f, y in parts:
            fl += f
            by += y
    return fl, by


def roofline(ctx):
    """Share of the roofline of every Gram-row kernel op in the window;
    None where the window ran none."""
    if ctx.summary is None or ctx.peaks is None:
        return None
    t = kernel_s(ctx.summary)
    if t <= 0 or "active_slots" not in ctx.counters:
        return None
    c = ctx.config
    b, n = int(c["batch_size"]), int(c["n"])
    w = b + int(c["tau"])
    flops = nbytes = 0.0
    for iters, counts, slots in zip(ctx.counters["iters"],
                                    ctx.counters["center_counts"],
                                    ctx.counters["active_slots"]):
        f, y = fit_work(iters, counts, slots, b=b, w=w, n=n,
                        route=ctx.counters["sqnorm_route"])
        flops += f
        nbytes += y
    peak = {**ctx.peaks,
            "bf16_flops_per_s": ctx.peaks["bf16_flops_per_s"]
            / HIGHEST_PASSES}
    return work.roofline_share(flops, nbytes, t, peak)[0]


def per_step_ms(ctx, seconds_of):
    """Device ms per iteration of ``seconds_of(summary)``; None where the
    window ran no Gram-row kernel op."""
    if ctx.summary is None or not ctx.counters.get("steps") \
            or kernel_s(ctx.summary) <= 0:
        return None
    return 1e3 * seconds_of(ctx.summary) / ctx.counters["steps"]
