"""AdamW with f32 master weights (params may live in bf16).

State layout (per parameter leaf): master (f32), mu (f32), nu (f32), each
shaped like the parameter.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class AdamWConfig(NamedTuple):
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup: int = 100


class AdamWState(NamedTuple):
    master: Any   # f32 pytree
    mu: Any
    nu: Any
    count: jax.Array


def adamw_init(params) -> AdamWState:
    # copy=True: with f32 params, astype would alias the parameter buffer
    # and break donation (same buffer donated twice via params AND master)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jnp.array(x, dtype=jnp.float32, copy=True), t)
    zeros = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return AdamWState(master=f32(params), mu=zeros(params),
                      nu=zeros(params), count=jnp.zeros((), jnp.int32))


def _schedule(cfg: AdamWConfig, count):
    warm = jnp.minimum(count.astype(jnp.float32) / max(cfg.warmup, 1), 1.0)
    return cfg.lr * warm


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_update(grads, state: AdamWState, params, cfg: AdamWConfig):
    """Returns (new_params [model dtype], new_state, metrics)."""
    count = state.count + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
    lr = _schedule(cfg, count)
    b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)

    def upd(g, m, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * g * g
        step = (mu / b1c) / (jnp.sqrt(nu / b2c) + cfg.eps)
        m = m - lr * (step + cfg.weight_decay * m)
        return m, mu, nu

    flat_g, tdef = jax.tree.flatten(grads)
    flat_m = jax.tree.leaves(state.master)
    flat_mu = jax.tree.leaves(state.mu)
    flat_nu = jax.tree.leaves(state.nu)
    out = [upd(g, m, mu, nu)
           for g, m, mu, nu in zip(flat_g, flat_m, flat_mu, flat_nu)]
    new_master = tdef.unflatten([o[0] for o in out])
    new_mu = tdef.unflatten([o[1] for o in out])
    new_nu = tdef.unflatten([o[2] for o in out])
    new_params = jax.tree.map(
        lambda m, p: m.astype(p.dtype), new_master, params)
    new_state = AdamWState(new_master, new_mu, new_nu, count)
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
