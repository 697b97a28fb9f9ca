"""Work the algorithm needs, counted over ACTIVE support rows.

Every center owns W = tau + b ring slots, but only slots with a non-zero
coefficient hold support.  A kernel that visits all W slots does work the
algorithm does not need; these functions count what it needs, whatever a
kernel visits, so a kernel that skips empty slots is held to the same
work and reads higher only by taking less time.

Counts for a Gaussian kernel between ``q`` query rows and ``a`` active
support rows of width ``d``:

* flops: the cross products, ``2 q a d`` (one multiply and one add per
  coordinate).  The exponentials, the coefficient contraction and the
  argmin are O(q a) and left out: they are below 1/d of the products.
* bytes: each active support row and each query row read once in f32,
  ``4 (a + q) d``, and nothing written back but O(q) results.

A share of the roofline is the least time these need on the chip,
``max(flops / bf16 peak, bytes / HBM bandwidth)``, over the measured time.
The flop bound uses the chip's bf16 peak: on the TPU the MXU contracts
these f32 operands in bf16 passes, and one pass is the least the chip can
do.  Which of the two bounds a share uses is returned with it.
"""
from __future__ import annotations

F32 = 4


def cross_work(q: int, a: int, d: int) -> tuple:
    """(flops, bytes) of one batch-by-support pass."""
    return 2.0 * q * a * d, float(F32 * (a + q) * d)


def fit_active_rows(step: int, iters: int, counts, w: int) -> float:
    """Active support rows before iteration ``step`` (1-based) of a fit of
    ``iters`` iterations whose centers took ``counts`` points in all, as
    the fitted state records them.  A center starts from one point and its
    ring holds at most W, so it holds ``min(W, 1 + c_j)`` rows once it has
    taken c_j points.  The state keeps no fill per iteration, so each
    center's points are spread evenly over the fit: c_j (step - 1) / iters
    before iteration ``step``.  At the fit's end this is the measured
    count of coef != 0 rows (the check's ``slots_err`` holds the state to
    it); a center that took few points counts few rows throughout."""
    frac = (step - 1) / iters
    return float(sum(min(w, 1.0 + c * frac) for c in counts))


def fit_work(iters: int, counts, b: int, w: int, d: int) -> tuple:
    """(flops, bytes) of both streaming passes over every iteration of one
    fit: the assignment against the centers before each iteration and the
    objective against the centers after it."""
    fl = by = 0.0
    for t in range(1, iters + 1):
        for a in (fit_active_rows(t, iters, counts, w),
                  fit_active_rows(t + 1, iters, counts, w)):
            f, y = cross_work(b, a, d)
            fl += f
            by += y
    return fl, by


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict):
    """-> (share in %, bound) with bound 'flops' or 'bytes'; None where
    nothing was timed."""
    if seconds <= 0:
        return None
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
