"""train_step: loss + grad + AdamW, with microbatch accumulation, remat'd
models, and an int8 error-feedback gradient-compression hook.

The step is pure and pjit-friendly: distribution comes entirely from the
shardings of TrainState/batch.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import forward_train
from repro.models.config import ModelConfig
from repro.train.optimizer import (
    AdamWConfig, AdamWState, adamw_init, adamw_update,
)


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    step: jax.Array
    ef_error: Any = None     # error-feedback buffer (grad compression)


def make_train_state(params, compress: bool = False) -> TrainState:
    ef = (jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
          if compress else None)
    return TrainState(params=params, opt=adamw_init(params),
                      step=jnp.zeros((), jnp.int32), ef_error=ef)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token CE.  logits (B,S,V) [any float dtype], labels (B,S)."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ------------------------------------------------- gradient compression
def quantize_int8(x: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compress_grads_ef(grads, ef_error):
    """int8 quantization with error feedback: the quantization residual is
    carried into the next step, so the *accumulated* update is unbiased
    (arXiv:1901.09847-style).  On real multi-pod hardware the int8 tensors
    are what crosses the 'pod' ICI links; here the quantize->dequantize
    round-trip exercises identical numerics."""

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = quantize_int8(g32)
        deq = q.astype(jnp.float32) * scale
        return deq, g32 - deq

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(ef_error)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return tdef.unflatten([o[0] for o in out]), \
        tdef.unflatten([o[1] for o in out])


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    microbatch: Optional[int] = None,
                    compress: bool = False):
    """Returns step(state, batch) -> (state, metrics).
    batch: {'tokens': (B,S), 'labels': (B,S)} (or 'embeds' for stub
    frontends).  `microbatch`: split B into that many accumulation chunks.
    """

    def loss_fn(params, batch):
        logits = forward_train(params, cfg, batch)
        return cross_entropy(logits[:, :-1], batch["labels"][:, :-1])

    def grads_of(params, batch):
        if microbatch is None or microbatch <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        b = batch["labels"].shape[0]
        mb = b // microbatch
        split = jax.tree.map(
            lambda x: x.reshape(microbatch, mb, *x.shape[1:]), batch)

        def acc(carry, micro):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(loss_fn)(params, micro)
            return (loss_acc + loss,
                    jax.tree.map(jnp.add, g_acc, g)), None

        zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                            params)
        (loss_sum, g_sum), _ = jax.lax.scan(acc, (jnp.zeros(()), zero),
                                            split)
        inv = 1.0 / microbatch
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    def step(state: TrainState, batch):
        loss, grads = grads_of(state.params, batch)
        ef = state.ef_error
        if compress:
            grads, ef = compress_grads_ef(grads, state.ef_error)
        params, opt, om = adamw_update(grads, state.opt, state.params,
                                       opt_cfg)
        new_state = TrainState(params=params, opt=opt,
                               step=state.step + 1, ef_error=ef)
        return new_state, {"loss": loss, **om}

    return step
