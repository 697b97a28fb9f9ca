"""Plain reference for Gaussian-kernel k-means with windowed centers.

A center is a weighted set of support points: C_j = sum_a c_ja phi(s_ja).
With the Gaussian kernel K(x, y) = exp(-|x - y|^2 / kappa), K(x, x) = 1 and

    d(x, C_j) = 1 - 2 sum_a c_ja K(x, s_ja) + <C_j, C_j>,
    <C_j, C_j> = sum_a sum_a' c_ja c_ja' K(s_ja, s_ja').

Straight ``jax.numpy`` over compact (k, A, d) support (A active rows per
center, zero coefficients on padding), in blocks so that it fits beside
the data.  It imports nothing of the system under test.

``precision="highest"`` computes in f32 with every product at the
"highest" matmul precision: the exact answer the comparisons measure
against.  ``precision="float8"`` is the lower-precision control: every
operand of a product (coordinates, kernel values, coefficients) is first
rounded to the 4 significant bits of an fp8 (e4m3) number, one step below
the bf16 operands the chip's f32 path multiplies in; sums, norms and
exponentials stay f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "float8")
HIGHEST = jax.lax.Precision.HIGHEST


def operand(a, precision: str):
    """``a`` as an operand of a product at ``precision``."""
    if precision == "highest":
        return a.astype(jnp.float32)
    if precision == "float8":
        m, e = jnp.frexp(a.astype(jnp.float32))
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def gaussian(a, b, kappa, precision: str = "highest"):
    """K(a_i, b_j), (m, n)."""
    a, b = operand(a, precision), operand(b, precision)
    ab = jnp.dot(a, b.T, precision=HIGHEST)
    aa = jnp.sum(a * a, axis=1)[:, None]
    bb = jnp.sum(b * b, axis=1)[None, :]
    return jnp.exp(-jnp.maximum(aa + bb - 2 * ab, 0) / kappa)


@functools.partial(jax.jit, static_argnames=("precision",))
def _norms_block(pts, coef, kappa, precision):
    def one(p, c):
        g = operand(gaussian(p, p, kappa, precision), precision)
        c = operand(c, precision)
        return jnp.dot(c, jnp.dot(g, c, precision=HIGHEST),
                       precision=HIGHEST)

    return jax.vmap(one)(pts, coef)


def center_norms(pts, coef, kappa, precision: str = "highest",
                 block: int = 16):
    """<C_j, C_j> for support pts (k, A, d) and coef (k, A)."""
    out = [_norms_block(pts[j:j + block], coef[j:j + block], kappa,
                        precision)
           for j in range(0, pts.shape[0], block)]
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("precision",))
def _dist_block(xq, pts, coef, norms, kappa, precision):
    k, a, d = pts.shape
    g = gaussian(xq, pts.reshape(k * a, d), kappa, precision)
    p = jnp.einsum("qka,ka->qk", operand(g, precision).reshape(-1, k, a),
                   operand(coef, precision), precision=HIGHEST)
    return 1.0 - 2.0 * p + norms[None, :]


def distances(xq, pts, coef, norms, kappa, precision: str = "highest",
              block: int = 1024):
    """d(x, C_j), (q, k), for queries xq (q, d), in blocks of ``block``
    rows (the last one padded, so every block runs one program)."""
    q = xq.shape[0]
    xq = jnp.pad(xq, ((0, -q % block), (0, 0)))
    out = [_dist_block(xq[i:i + block], pts, coef, norms, kappa, precision)
           for i in range(0, xq.shape[0], block)]
    return jnp.concatenate(out)[:q]


def nearest(xq, pts, coef, kappa, precision: str = "highest"):
    """Labels of the nearest center at ``precision``."""
    norms = center_norms(pts, coef, kappa, precision)
    return jnp.argmin(distances(xq, pts, coef, norms, kappa, precision),
                      axis=1)
