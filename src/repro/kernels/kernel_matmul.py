"""Pallas TPU kernel: streaming kernel-matrix matmul  (K(x, y) @ v).

The full-batch baseline (Lloyd in feature space), kernel k-means++ and the
<C,C> Gram recompute all reduce to (K(x,y) @ v) with a skinny v.  The naive
path materializes the (n, m) kernel matrix — 19.6 GB for MNIST n = 70k f32 —
and is pure HBM traffic.  This kernel computes K tiles in VMEM from x/y
tiles (FlashAttention-style) and contracts immediately:

    HBM traffic:  O(n*d + m*(d + c) + n*c)   instead of O(n*m).
    grid = (n/nt, m/mt), m innermost; out block (nt, c) stays resident.

Arithmetic intensity rises from ~1 flop/byte (kernel matrix read) to
~min(nt, mt) flop/byte — firmly compute-bound on the MXU for 128x128 tiles.
Squared norms enter as an (n, 1) column and a (1, m) row, so their blocks
keep Mosaic's 2-D (8, 128) tiling; 1-D blocks get an XLA layout that
Mosaic rejects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_assign import _apply_kernel


def _km_body(x_ref, xsq_ref, y_ref, ysq_ref, v_ref, out_ref,
             *, kind, p0, p1, p2):
    im = pl.program_id(1)

    @pl.when(im == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # (nt, d)
    y = y_ref[...].astype(jnp.float32)          # (mt, d)
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (nt, mt)
    kv = _apply_kernel(xy, xsq_ref[...], ysq_ref[...], kind, p0, p1, p2)
    out_ref[...] += kv @ v_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "kind", "p0", "p1", "p2", "nt", "mt", "interpret"))
def kernel_matmul_pallas(x: jax.Array, y: jax.Array, v: jax.Array, *,
                         kind: str = "gaussian", p0: float = 1.0,
                         p1: float = 1.0, p2: int = 2,
                         nt: int = 128, mt: int = 128,
                         interpret: bool = False) -> jax.Array:
    """x: (n, d); y: (m, d); v: (m, c) -> (n, c) f32.

    Padding: m-padding rows get v = 0 (no contribution for any kernel);
    n-padding rows are sliced off; d zero-padded (distance/dot preserving).
    """
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    m, c = v.shape

    np_ = -n % nt
    mp = -m % mt
    dp = -d % 128
    cp = -c % 128
    x_p = jnp.pad(x, ((0, np_), (0, dp)))
    y_p = jnp.pad(y, ((0, mp), (0, dp)))
    v_p = jnp.pad(v, ((0, mp), (0, cp)))
    xsq = jnp.sum(x_p.astype(jnp.float32) ** 2, axis=-1)[:, None]  # (n+, 1)
    ysq = jnp.sum(y_p.astype(jnp.float32) ** 2, axis=-1)[None, :]  # (1, m+)

    nn, dd = x_p.shape
    mm = y_p.shape[0]
    cc = v_p.shape[1]
    grid = (nn // nt, mm // mt)

    out = pl.pallas_call(
        functools.partial(_km_body, kind=kind, p0=p0, p1=p1, p2=p2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nt, dd), lambda i, im: (i, 0)),
            pl.BlockSpec((nt, 1), lambda i, im: (i, 0)),
            pl.BlockSpec((mt, dd), lambda i, im: (im, 0)),
            pl.BlockSpec((1, mt), lambda i, im: (0, im)),
            pl.BlockSpec((mt, cc), lambda i, im: (im, 0)),
        ],
        out_specs=pl.BlockSpec((nt, cc), lambda i, im: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nn, cc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_p, xsq, y_p, ysq, v_p)
    return out[:n, :c]
