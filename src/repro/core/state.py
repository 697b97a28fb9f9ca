"""Fixed-shape truncated-center state for Algorithm 2.

The paper maintains each center as a sparse combination of the points in the
most recent batches Q_i^j ("smallest suffix with >= tau points").  On TPU we
need fixed shapes, so each center owns a ring buffer of W = tau + b point
slots.  Overwriting the oldest slot individually (instead of dropping whole
batches) keeps the window at >= tau most-recent points once full, which is
exactly the property Lemma 3's decay bound needs (see DESIGN.md §3).

Invariants:
* slot with ``coef == 0`` is empty; its ``idx`` is 0 (a valid gather index —
  the zero coefficient nullifies the contribution).
* while the initial (k-means++) point has not been evicted, the truncated
  center EQUALS the exact Algorithm-1 center (paper's ``min Q = 1`` case).
* ``sqnorm[j] == <C_j, C_j>`` in feature space at all times.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import KernelFn, kernel_diag
from repro.core.loop import scope


class CenterState(NamedTuple):
    idx: jax.Array      # (k, W) int32 — indices into the dataset
    coef: jax.Array     # (k, W) f32   — coefficient on phi(X[idx])
    head: jax.Array     # (k,)   int32 — next ring write position
    sqnorm: jax.Array   # (k,)   f32   — <C_j, C_j>
    counts: jax.Array   # (k,)   f32   — lifetime #points assigned (sklearn rate)
    step: jax.Array     # ()     int32

    @property
    def k(self) -> int:
        return self.idx.shape[0]

    @property
    def window(self) -> int:
        return self.idx.shape[1]


def init_state(x: jax.Array, center_idx: jax.Array, kernel: KernelFn,
               window: int) -> CenterState:
    """Centers start as single data points (k-means++ / random init picks
    indices), occupying slot 0 with coefficient 1."""
    k = center_idx.shape[0]
    with scope("kkm.init"):
        idx = jnp.pad(center_idx.astype(jnp.int32)[:, None],
                      ((0, 0), (0, window - 1)))
        coef = jnp.zeros((k, window), jnp.float32).at[:, 0].set(1.0)
        return CenterState(
            idx=idx,
            coef=coef,
            head=jnp.ones((k,), jnp.int32),
            sqnorm=kernel_diag(kernel, x[center_idx]).astype(jnp.float32),
            counts=jnp.zeros((k,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )


def window_size(batch_size: int, tau: int) -> int:
    """W = tau + b: a full ring always retains >= tau points newer than any
    evicted point (Lemma 3's requirement)."""
    return tau + batch_size
