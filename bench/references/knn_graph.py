"""Plain reference for kernel k-means on the k-nn graph kernel.

The kernel is the paper's Appendix C k-nn Gram (arXiv:2410.05902):
G = D^-1 A D^-1, A the union-symmetrized 0/1 adjacency of each row's
k + 1 nearest rows (itself among them) with self-loops, D its row sums.
``build`` makes it from the points on the host, with exact float64
distances, and imports nothing of the system under test.

With index data a center's support "points" are row ids, (k, A, 1) as
``benchlib.checks.compact`` gathers them, and every kernel value is a
Gram lookup:

    d(x, C_j) = G[x, x] - 2 sum_a c_ja G[x, s_ja] + <C_j, C_j>,
    <C_j, C_j> = sum_a sum_a' c_ja c_ja' G[s_ja, s_ja'].

Sums run in float64 on the host.  ``precision="highest"`` takes the
Gram's f32 values and the coefficients as they are: the exact answer the
comparisons measure against.  A lower precision rounds every operand of
a product (Gram values and coefficients) first: ``"bfloat16"`` to the 8
significant bits a bf16 holds, what the chip's default f32 matmul
multiplies, and ``"float8"`` to the 4 of an fp8 (e4m3) number, at any
exponent.

Rows whose k-th and (k+1)-th nearest distances lie within ``tie_tol`` of
each other (f32 rounding of the program's distances can order them
either way) are the only place two exact builds may differ; ``build``
marks them and the rows that could swap in or out of their lists, and
``gram_err`` leaves those rows and columns out (a swapped edge changes
the degree, so the whole row and column, of both of its ends).
"""
from __future__ import annotations

import dataclasses

import numpy as np

PRECISIONS = ("highest", "bfloat16", "float8")
BITS = {"bfloat16": 8, "float8": 4}


@dataclasses.dataclass
class Graph:
    gram: np.ndarray        # (n, n) f32
    ties: np.ndarray        # (n,) bool: rows whose k-th neighbour ties
    affected: np.ndarray    # (n,) bool: tie rows and their candidates


def tie_tol(sq: np.ndarray) -> np.ndarray:
    """Per row, how close two squared distances lie before f32 rounding
    of |x_i|^2 + |x_j|^2 - 2 x_i . x_j can order them either way: 2^-19
    of the terms' size, 32 f32 ulps of it (the sums of d = 16 squares
    and products each round to a few)."""
    return 2.0 ** -19 * (sq + sq.max())


def build(x, k: int, block: int = 1024) -> Graph:
    """The k-nn Gram of the rows of ``x`` (n, d), ties to the lower id."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    sq = (x * x).sum(1)
    tol = tie_tol(sq)
    a = np.zeros((n, n), np.float32)
    ties = np.zeros(n, bool)
    affected = np.zeros(n, bool)
    m = min(k + 2, n)
    for i0 in range(0, n, block):
        rows = np.arange(i0, min(i0 + block, n))
        d2 = sq[rows, None] + sq[None, :] - 2.0 * (x[rows] @ x.T)
        cand = np.argpartition(d2, m - 1, axis=1)[:, :m]
        dc = np.take_along_axis(d2, cand, axis=1)
        order = np.lexsort((cand, dc), axis=1)
        cand = np.take_along_axis(cand, order, axis=1)
        dc = np.take_along_axis(dc, order, axis=1)
        a[np.repeat(rows, k + 1), cand[:, :k + 1].ravel()] = 1.0
        if m > k + 1:
            t = dc[:, k + 1] - dc[:, k] <= tol[rows]
            ties[rows] = t
            for r in np.flatnonzero(t):
                edge = 0.5 * (dc[r, k] + dc[r, k + 1])
                near = np.abs(d2[r] - edge) <= tol[rows[r]]
                affected[near] = True
                affected[rows[r]] = True
    a = np.maximum(a, a.T)
    np.fill_diagonal(a, 1.0)
    dinv = (1.0 / a.sum(1)).astype(np.float32)
    gram = (dinv[:, None] * a) * dinv[None, :]
    return Graph(gram=gram, ties=ties, affected=affected)


def gram_err(got, graph: Graph) -> float:
    """Widest gap between a Gram and the reference's, over the rows and
    columns no tie touches, as a share of the reference's largest entry."""
    keep = ~graph.affected
    got = np.asarray(got)[keep][:, keep]
    want = graph.gram[keep][:, keep]
    return float(np.abs(got - want).max() / graph.gram.max())


def operand(a, precision: str):
    """``a`` as an operand of a product at ``precision`` (float64)."""
    a = np.asarray(a, np.float64)
    if precision == "highest":
        return a
    if precision not in BITS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    m, e = np.frexp(a)
    scale = 2.0 ** BITS[precision]
    return np.ldexp(np.round(m * scale) / scale, e)


def _ids(pts) -> np.ndarray:
    return np.rint(np.asarray(pts, np.float64)[..., 0]).astype(np.int64)


def center_norms(pts, coef, gram, precision: str = "highest"):
    """<C_j, C_j> for support ids pts (k, A, 1) and coef (k, A)."""
    ids, coef = _ids(pts), np.asarray(coef)
    out = np.empty(ids.shape[0])
    for j in range(ids.shape[0]):
        c = operand(coef[j], precision)
        g = operand(gram[np.ix_(ids[j], ids[j])], precision)
        out[j] = c @ (g @ c)
    return out


def distances(xq, pts, coef, norms, gram, precision: str = "highest",
              block: int = 512):
    """d(x, C_j), (q, k), for query ids xq (q, 1)."""
    q = _ids(xq)
    ids = _ids(pts)
    k, a = ids.shape
    c = operand(coef, precision)
    out = np.empty((q.shape[0], k))
    for i0 in range(0, q.shape[0], block):
        qb = q[i0:i0 + block]
        g = operand(gram[np.ix_(qb, ids.reshape(-1))], precision)
        p = np.einsum("qka,ka->qk", g.reshape(-1, k, a), c)
        out[i0:i0 + block] = (gram[qb, qb].astype(np.float64)[:, None]
                              - 2.0 * p + np.asarray(norms)[None, :])
    return out


def nearest(xq, pts, coef, gram, precision: str = "highest"):
    """Labels of the nearest center at ``precision``."""
    norms = center_norms(pts, coef, gram, precision)
    return np.argmin(distances(xq, pts, coef, norms, gram, precision),
                     axis=1)


class Bound:
    """This reference on one Gram, in the calling convention of
    ``benchlib.checks.fit_numbers`` (``kappa`` is passed and ignored: the
    Gram kernel has no bandwidth).  ``fit_numbers`` asks its control for
    ``"float8"``; every precision other than ``"highest"`` reads as
    ``control`` here, so the same comparison runs the bf16 control too."""

    def __init__(self, gram, control: str = "float8"):
        if control not in BITS:
            raise ValueError(f"control {control!r} not in {sorted(BITS)}")
        self.gram, self.control = gram, control

    def _p(self, precision):
        return "highest" if precision == "highest" else self.control

    def operand(self, a, precision):
        return operand(a, self._p(precision))

    def center_norms(self, pts, coef, kappa=None, precision="highest"):
        return center_norms(pts, coef, self.gram, self._p(precision))

    def distances(self, xq, pts, coef, norms, kappa=None,
                  precision="highest"):
        return distances(xq, pts, coef, norms, self.gram,
                         self._p(precision))

    def nearest(self, xq, pts, coef, kappa=None, precision="highest"):
        return nearest(xq, pts, coef, self.gram, self._p(precision))
