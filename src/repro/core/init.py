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

import functools

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import KernelFn, kernel_cross, kernel_diag
from repro.core.loop import _kernel_sig, lookup_program, scope, span


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


def _draw(method: str, k: int, kernel: KernelFn, key: jax.Array,
          x: jax.Array) -> jax.Array:
    with scope("kkm.init"):
        if method == "kmeans++":
            return kmeans_plus_plus(key, x, k, kernel)
        return random_init(key, x.shape[0], k)


def _draw_program(k: int, kernel: KernelFn, method: str):
    """The compiled draw ``(key, x) -> (k,) int32`` for (method, k, kernel
    signature), built once through the loop core's program registry and
    reused across fits (``jit`` keys x's shape, dtype and sharding).  As
    for the fit program (``SingleExecutor._jit_run``), a kernel whose
    leaves key by value is closed over, its scalars compile-time
    constants; a kernel too large to key by value (a Precomputed Gram, a
    cached kernel) is the program's first, traced argument, so a new Gram
    of the same shape reuses the program and is never baked in."""
    as_arg = _kernel_sig(kernel) is None

    def build():
        if as_arg:
            def draw(kern, key, x):
                return _draw(method, k, kern, key, x)
        else:
            def draw(key, x):
                return _draw(method, k, kernel, key, x)
        return jax.jit(draw)

    # an empty instance cache: the registry's key carries the kernel
    # signature, so a draw for one kappa never serves another
    prog = lookup_program({}, "draw_init", (method, k, as_arg), build,
                          kernel=kernel, kernel_free=as_arg)
    return functools.partial(prog, kernel) if as_arg else prog


def draw_init(key: jax.Array, x: jax.Array, k: int, kernel: KernelFn,
              method: str = "kmeans++") -> jax.Array:
    """The one init-drawing entry every fit path shares (it used to be
    copy-pasted across ``fit`` / ``fit_cached`` / the engine): dispatch on
    the method name, return (k,) int32 indices into ``x``.  The draw is a
    compiled program (:func:`_draw_program`), so a fit only dispatches it
    and the device runs it and the fit program back to back."""
    if method not in ("kmeans++", "random"):
        raise ValueError(f"unknown init method {method!r} "
                         "(expected 'kmeans++' or 'random')")
    with span("kkm.init"):
        return _draw_program(k, kernel, method)(key, x)
