"""Graph-based kernels from the paper's Appendix C.

* k-nn kernel:  gram = D^-1 A D^-1, A = symmetrized k-nn adjacency with
  self-loops (self-loops keep K(x,x) > 0 so gamma is well defined; the
  paper's Table 1 reports gamma ~ 1e-3 for this kernel — it is D^-1's
  doing, and our construction reproduces that scale).
* heat kernel:  gram = expm(-t * L),  L = I - D^-1/2 A D^-1/2, via
  eigendecomposition (symmetric => PSD for every t).  NOTE: the paper's
  Appendix C literally writes exp(-t D^-1/2 A D^-1/2), but cites Chung
  (1997), whose heat kernel is e^{-tL}; the literal formula inverts the
  spectrum (up-weights high-frequency eigenvectors), so we implement
  Chung's definition.  gamma << 1 here matches the paper's Table 1.

These return `Precomputed` kernels whose "data" is the (n, 1) index array —
see repro.core.kernel_fns.  The k-nn graph is built on the device: squared
distances of ``KNN_BLOCK`` rows at a time against all n at
``Precision.HIGHEST`` (so no (n, n) distance matrix lives beside the
Gram), ``lax.top_k`` for each row's k + 1 nearest rows (itself among
them), then the symmetrized adjacency and D^-1 A D^-1 in f32, all in one
program under the ``kkm.gram`` span and stage.  Exact O(n^2 d); the paper
treats kernel construction as a separate preprocessing cost (the black bar
in Figure 1) and so do we.  The heat kernel's eigendecomposition stays on
the host, O(n^3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_fns import Precomputed
from repro.core.loop import scope, span

KNN_BLOCK = 1024


def _knn_ids(x: jax.Array, k: int, block: int) -> jax.Array:
    """(n, k + 1) ids of each row's k + 1 nearest rows by squared
    Euclidean distance, |x_i|^2 + |x_j|^2 - 2 x_i . x_j in f32 at
    ``Precision.HIGHEST``; ties go to the lower id."""
    n, d = x.shape
    sq = jnp.sum(x * x, axis=1)
    bs = min(block, n)
    xp = jnp.pad(x, ((0, -n % bs), (0, 0)))

    def rows(xr):
        d2 = (jnp.sum(xr * xr, axis=1)[:, None] + sq[None, :]
              - 2.0 * jnp.dot(xr, x.T, precision=jax.lax.Precision.HIGHEST))
        return jax.lax.top_k(-d2, k + 1)[1]

    return jax.lax.map(rows, xp.reshape(-1, bs, d)).reshape(-1, k + 1)[:n]


def _adjacency(x: jax.Array, k: int, block: int) -> jax.Array:
    """Symmetrized (union) 0/1 k-nn adjacency with self-loops, (n, n)."""
    n = x.shape[0]
    ids = _knn_ids(x, k, block)
    a = jnp.zeros((n, n), jnp.float32).at[jnp.arange(n)[:, None],
                                          ids].set(1.0)
    a = jnp.maximum(a, a.T)
    return a.at[jnp.arange(n), jnp.arange(n)].set(1.0)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _adjacency_jit(x, *, k, block):
    with scope("kkm.gram"):
        return _adjacency(x, k, block)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _knn_gram(x, *, k, block):
    with scope("kkm.gram"):
        a = _adjacency(x, k, block)
        dinv = 1.0 / jnp.sum(a, axis=1)
        return (dinv[:, None] * a) * dinv[None, :]


def knn_adjacency(x, k: int = 10) -> jax.Array:
    """Symmetrized k-nn 0/1 adjacency with self-loops, exact O(n^2 d), on
    the device."""
    with span("kkm.gram"):
        return _adjacency_jit(jnp.asarray(x, jnp.float32), k=k,
                              block=KNN_BLOCK)


def knn_kernel(x, k: int = 10):
    """gram = D^-1 A D^-1, built on the device; returns (Precomputed,
    index_data (n,1) f32)."""
    x = jnp.asarray(x, jnp.float32)
    with span("kkm.gram"):
        gram = _knn_gram(x, k=k, block=KNN_BLOCK)
    idx = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
    return Precomputed(gram=gram), idx


def heat_kernel(x, k: int = 10, t: float = 1.0):
    """gram = expm(-t (I - D^-1/2 A D^-1/2)) (Chung 1997), PSD for all t;
    the eigendecomposition runs on the host in float64."""
    a = np.asarray(knn_adjacency(x, k))
    dq = 1.0 / np.sqrt(a.sum(1))
    m = (dq[:, None] * a) * dq[None, :]
    w, u = np.linalg.eigh(m.astype(np.float64))
    gram = (u * np.exp(-t * (1.0 - w))[None, :]) @ u.T
    idx = np.arange(a.shape[0], dtype=np.float32)[:, None]
    return Precomputed(gram=gram.astype(np.float32)), idx
