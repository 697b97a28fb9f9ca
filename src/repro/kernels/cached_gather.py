"""Pallas kernel: assignment dots from cache-resolved Gram rows.

Computes   P[i, j] = sum_w coef[j, w] * rows[i, sup_ids[j, w]]
where ``rows`` are the batch's Gram rows K(x_B, x) already resolved through
the Gram tile cache (repro.cache) — so the assignment step of Algorithm 2
performs ZERO kernel evaluations, and the (b, k*W) gathered cross block is
never materialized in HBM.

The column gather is turned into a contraction: the window coefficients
are scattered once into a dense (k, n) weight matrix V (duplicate ids add,
pad slots with coef == 0 add nothing), and the kernel computes
P = rows @ V^T tile by tile.

TPU mapping:
* grid = (b/bt, n/nt); the inner axis streams (bt, nt) tiles of the Gram
  rows and (k, nt) tiles of V through VMEM, so no block spans a whole row
  (a (128, 65536) f32 row block alone would be 32 MiB of VMEM).
* Each step is one (bt, nt) x (nt, k) MXU matmul accumulated into the
  (bt, k) output block, which stays resident across the n axis.
* VMEM working set per step: bt*nt + k*nt + bt*k floats (about 0.6 MB at
  bt=128, nt=512, k=64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gather_body(rows_ref, v_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        rows_ref[...].astype(jnp.float32), v_ref[...],
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _window_weights(sup_ids: jax.Array, coef: jax.Array,
                    n: int) -> jax.Array:
    """(k, n) dense weights: V[j, c] = sum over w with sup_ids[j, w] == c of
    coef[j, w]."""
    k, _ = coef.shape
    return jnp.zeros((k, n), jnp.float32).at[
        jnp.arange(k)[:, None], sup_ids].add(coef.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bt", "nt", "interpret"))
def cached_assign_dots_pallas(rows: jax.Array, sup_ids: jax.Array,
                              coef: jax.Array, *, bt: int = 128,
                              nt: int = 512,
                              interpret: bool = False) -> jax.Array:
    """rows: (b, n) f32; sup_ids: (k, W) int32; coef: (k, W) -> P (b, k)."""
    from jax.experimental.pallas import tpu as pltpu

    b, n = rows.shape
    k, _ = coef.shape

    bp = -b % bt
    np_ = -n % nt
    rows_p = jnp.pad(rows, ((0, bp), (0, np_)))
    v = jnp.pad(_window_weights(sup_ids.astype(jnp.int32), coef, n),
                ((0, 0), (0, np_)))

    bb, nn = rows_p.shape
    grid = (bb // bt, nn // nt)

    out = pl.pallas_call(
        _gather_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, nt), lambda ib, jn: (ib, jn)),
            pl.BlockSpec((k, nt), lambda ib, jn: (0, jn)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda ib, jn: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rows_p, v)
    return out[:b]
