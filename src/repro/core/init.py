"""Center initialization.

Kernel k-means++ (Arthur & Vassilvitskii 2007, run in feature space): pick
the first center uniformly, then sample each next center with probability
proportional to the squared feature-space distance to the closest chosen
center.  Because chosen centers are single data points, d^2(x, c) =
K(x,x) + K(c,c) - 2 K(x,c) — O(n) kernel evaluations per center, O(nk)
total.  Theorem 1(3): this initialization gives the O(log k) expected
approximation ratio.

All functions return center INDICES into X — every algorithm in repro.core
represents centers as (sparse) combinations of data points, so an index is
the canonical initial center.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import KernelFn, kernel_cross, kernel_diag
from repro.core.loop import scope, span


def kmeans_plus_plus(key: jax.Array, x: jax.Array, k: int,
                     kernel: KernelFn) -> jax.Array:
    """D^2-sampling in feature space; returns (k,) int32 indices into x."""
    n = x.shape[0]
    diag = kernel_diag(kernel, x)  # (n,) = K(x,x)

    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)

    def dist_to(idx):
        c = x[idx][None, :]
        cross = kernel_cross(kernel, x, c)[:, 0]  # (n,)
        return jnp.maximum(diag + diag[idx] - 2.0 * cross, 0.0)

    def body(t, carry):
        mind, chosen, key = carry
        key, sub = jax.random.split(key)
        # Guard against an all-zero distance vector (duplicate data): fall
        # back to uniform.
        total = jnp.sum(mind)
        p = jnp.where(total > 0, mind / jnp.maximum(total, 1e-30),
                      jnp.full_like(mind, 1.0 / n))
        nxt = jax.random.choice(sub, n, p=p)
        chosen = chosen.at[t].set(nxt)
        mind = jnp.minimum(mind, dist_to(nxt))
        return mind, chosen, key

    chosen = jnp.zeros((k,), jnp.int32).at[0].set(first)
    mind = dist_to(first)
    mind, chosen, _ = jax.lax.fori_loop(1, k, body, (mind, chosen, key))
    return chosen


def kmeans_plus_plus_subsampled(key: jax.Array, x: jax.Array, k: int,
                                kernel: KernelFn, m: int) -> jax.Array:
    """k-means++ over a uniform subsample of size m — sublinear-in-n init
    for the truly huge regime (composes with the paper's O(1)-iteration
    result for b = Theta(log n))."""
    ks, kp = jax.random.split(key)
    sub = jax.random.choice(ks, x.shape[0], (m,), replace=False)
    local = kmeans_plus_plus(kp, x[sub], k, kernel)
    return sub[local]


def random_init(key: jax.Array, n: int, k: int) -> jax.Array:
    return jax.random.choice(key, n, (k,), replace=False).astype(jnp.int32)


def draw_init(key: jax.Array, x: jax.Array, k: int, kernel: KernelFn,
              method: str = "kmeans++") -> jax.Array:
    """The one init-drawing entry every fit path shares (it used to be
    copy-pasted across ``fit`` / ``fit_cached`` / the engine): dispatch on
    the method name, return (k,) int32 indices into ``x``."""
    with span("kkm.init"), scope("kkm.init"):
        if method == "kmeans++":
            return kmeans_plus_plus(key, x, k, kernel)
        if method == "random":
            return random_init(key, x.shape[0], k)
    raise ValueError(f"unknown init method {method!r} "
                     "(expected 'kmeans++' or 'random')")
