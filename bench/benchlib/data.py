"""Data made on the device from ``--seed``.

A configuration's ``data`` block names a mixture and its parameters:
``prototypes``, ``centers`` prototypes uniform in [0, 1]^d, each point a
prototype plus ``noise`` * N(0, I_d) (pixel noise on MNIST-shaped rows).

The mixture (its centers) comes from the seed; every draw of points is
one jitted call from a key, in f32, straight into device memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (also past 32 bits) and a
    stream number, so each use of the seed draws independently."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=("kind", "centers", "d"))
def mixture(key, *, kind: str, centers: int, d: int):
    if kind == "prototypes":
        return jax.random.uniform(key, (centers, d), jnp.float32)
    raise ValueError(f"unknown mixture {kind!r}")


@functools.partial(jax.jit, static_argnames=("n",))
def draw(key, centers, scale, *, n: int):
    """(n, d) points of the mixture and their component ids."""
    ky, kx = jax.random.split(key)
    y = jax.random.randint(ky, (n,), 0, centers.shape[0])
    noise = jax.random.normal(kx, (n, centers.shape[1]), jnp.float32)
    return centers[y] + scale * noise, y


def make_mixture(config: dict, seed: int):
    data = config["data"]
    return mixture(seed_key(seed, 1), kind=data["kind"],
                   centers=int(data["centers"]), d=int(config["d"]))


def points(config: dict, centers, seed: int, stream: int, n: int):
    """n points of the configuration's mixture, drawn from (seed, stream)."""
    x, y = draw(seed_key(seed, stream), centers,
                float(config["data"]["noise"]), n=int(n))
    return x, y

