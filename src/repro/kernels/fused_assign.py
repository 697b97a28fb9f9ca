"""Pallas TPU kernel: fused kernel-distance x coefficient contraction.

Computes   P[i, j] = sum_w coef[j, w] * K(xb[i], sup[j, w])
— the hot loop of Algorithm 2's assignment step (Theorem 1(1)'s O(k (tau+b))
term) — WITHOUT materializing the (b, k*W) cross-kernel matrix in HBM.

TPU mapping:
* grid = (b/bt, k, W/st); the innermost axis streams support tiles.
* Each step: one (bt, d) x (d, st) MXU matmul for the cross products, VPU
  exp for the Gaussian, then a lane reduction of the (bt, st) kernel tile
  against the (1, st) coefficient row into a (bt, 1) VMEM accumulator; at
  the last support tile the accumulator lands in column j of the (bt, k)
  output block, which stays resident across the two inner grid axes.
* Per-center vectors (support squared norms, coefficients) are (k, 1, W)
  views with squeezed (1, st) row blocks, and row norms are (b, 1)
  columns, so every block satisfies Mosaic's (8, 128) tiling rule.
* VMEM working set per step: bt*d + st*d + bt*st + bt*k floats
  (about 1.2 MB at the default tiles, d=1024, k=256).
* Supported kernels: gaussian / linear / polynomial (MXU-friendly);
  laplacian needs an L1 distance (no matmul form) and falls back to the
  XLA path in ops.py.

Block sizes are parameters; tests sweep small tiles in interpret mode, the
TPU default is (128, 128) with d padded to a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _apply_kernel(xy, xsq, ysq, kind: str, p0: float, p1: float, p2: int):
    """Elementwise kernel from cross products (f32) and squared norms
    given as a column ``xsq`` (bt, 1) and a row ``ysq`` (1, st)."""
    if kind == "gaussian":
        d2 = jnp.maximum(xsq + ysq - 2.0 * xy, 0.0)
        return jnp.exp(-d2 / p0)
    if kind == "linear":
        return xy
    if kind == "polynomial":
        return (xy / p1 + p0) ** p2
    raise ValueError(kind)


def _fused_body(x_ref, xsq_ref, sup_ref, supsq_ref, coef_ref, out_ref,
                acc_ref, *, kind, p0, p1, p2):
    j = pl.program_id(1)
    iw = pl.program_id(2)

    @pl.when((j == 0) & (iw == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(iw == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (bt, d)
    s = sup_ref[...].astype(jnp.float32)        # (st, d)
    xy = jax.lax.dot_general(x, s, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bt, st)
    kv = _apply_kernel(xy, xsq_ref[...], supsq_ref[...], kind, p0, p1, p2)
    c = coef_ref[...].astype(jnp.float32)       # (1, st)
    acc_ref[...] += jnp.sum(kv * c, axis=1, keepdims=True)

    @pl.when(iw == pl.num_programs(2) - 1)
    def _store():
        col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(col == j, acc_ref[...], out_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "kind", "p0", "p1", "p2", "bt", "st", "interpret"))
def fused_batch_center_dots_pallas(
        xb: jax.Array, sup: jax.Array, coef: jax.Array, *,
        kind: str = "gaussian", p0: float = 1.0, p1: float = 1.0,
        p2: int = 2, bt: int = 128, st: int = 128,
        interpret: bool = False) -> jax.Array:
    """xb: (b, d); sup: (k, W, d); coef: (k, W) -> P (b, k) f32.

    b, W, d are padded to tile multiples here (zero points with zero
    coefficients contribute nothing for every supported kernel)."""
    from jax.experimental.pallas import tpu as pltpu

    b, d = xb.shape
    k, w, _ = sup.shape

    bp = -b % bt
    wp = -w % st
    dp = -d % 128
    xb_p = jnp.pad(xb, ((0, bp), (0, dp)))
    sup_p = jnp.pad(sup, ((0, 0), (0, wp), (0, dp)))
    coef_p = jnp.pad(coef, ((0, 0), (0, wp)))[:, None, :]          # (k,1,W+)
    xsq = jnp.sum(xb_p.astype(jnp.float32) ** 2, axis=-1,
                  keepdims=True)                                   # (b+, 1)
    supsq = jnp.sum(sup_p.astype(jnp.float32) ** 2, axis=-1)[:, None, :]

    bb, dd = xb_p.shape
    ww = sup_p.shape[1]
    grid = (bb // bt, k, ww // st)

    out = pl.pallas_call(
        functools.partial(_fused_body, kind=kind, p0=p0, p1=p1, p2=p2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, dd), lambda ib, j, iw: (ib, 0)),
            pl.BlockSpec((bt, 1), lambda ib, j, iw: (ib, 0)),
            pl.BlockSpec((None, st, dd), lambda ib, j, iw: (j, iw, 0)),
            pl.BlockSpec((None, 1, st), lambda ib, j, iw: (j, 0, iw)),
            pl.BlockSpec((None, 1, st), lambda ib, j, iw: (j, 0, iw)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda ib, j, iw: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xb_p, xsq, sup_p, supsq, coef_p)
    return out[:b]
