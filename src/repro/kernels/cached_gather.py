"""Pallas kernel: assignment dots from Gram rows.

Computes   P[i, j] = sum_w coef[j, w] * rows[i, sup_ids[j, w]]
where ``rows`` are full Gram rows K(x_B, x): resolved through the Gram
tile cache (repro.cache), or gathered from a ``Precomputed`` Gram — so
the assignment step of Algorithm 2 performs ZERO kernel evaluations, and
the (b, k*W) gathered cross block is never materialized in HBM.

The column gather is turned into a contraction: the window coefficients
are scattered once into a dense (k, n) weight matrix V
(:func:`window_weights`: duplicate ids add, pad slots with coef == 0 add
nothing), and the kernel computes P = rows @ V^T tile by tile at
``Precision.HIGHEST`` (f32 operands, f32 accumulation).

TPU mapping:
* grid = (cdiv(b, bt), cdiv(n, nt)); the inner axis streams (bt, nt)
  tiles of the Gram rows and (k, nt) tiles of V through VMEM, so no block
  spans a whole row (a (128, 65536) f32 row block alone would be 32 MiB
  of VMEM).
* Nothing is padded in HBM: a last tile that overhangs b or n reads
  unspecified values there.  Overhanging rows only write output rows that
  are discarded; overhanging columns are masked to zero in both operands
  before the product, so a Gram of any n (10,992 is off every 128 tile)
  is read in place.
* Each step is one (bt, nt) x (nt, k) MXU matmul accumulated into the
  (bt, k) output block, which stays resident across the n axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# a (bt, nt) f32 row tile is at most this many bytes: two of them (the
# pipeline's double buffer) stay well inside the 16 MiB of scoped VMEM
_ROW_TILE_BYTES = 4 * 2 ** 20


def row_tile(b: int, nt: int) -> int:
    """bt for ``b`` Gram rows in (bt, nt) tiles: the whole batch, rounded
    up to 8 sublanes, in as few row tiles as keep a tile within 4 MiB — so
    V is streamed once per row tile, and a 2048-row batch takes one."""
    return max(8, min(-(-b // 8) * 8, _ROW_TILE_BYTES // (4 * nt) // 8 * 8))


def _gather_body(rows_ref, v_ref, out_ref, *, n: int, nt: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...].astype(jnp.float32)
    v = v_ref[...]
    if n % nt:
        col = j * nt + jax.lax.broadcasted_iota(jnp.int32, (1, nt), 1)
        rows = jnp.where(col < n, rows, 0.0)
        v = jnp.where(col < n, v, 0.0)
    out_ref[...] += jax.lax.dot_general(
        rows, v, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def window_weights(sup_ids: jax.Array, coef: jax.Array,
                   n: int) -> jax.Array:
    """(k, n) dense weights: V[j, c] = sum over w with sup_ids[j, w] == c of
    coef[j, w]."""
    k, _ = coef.shape
    return jnp.zeros((k, n), jnp.float32).at[
        jnp.arange(k)[:, None], sup_ids.astype(jnp.int32)].add(
            coef.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bt", "nt", "interpret"))
def cached_assign_dots_pallas(rows: jax.Array, v: jax.Array, *,
                              bt: int | None = None, nt: int = 512,
                              interpret: bool = False) -> jax.Array:
    """rows: (b, n) Gram rows; v: (k, n) f32 window weights -> P (b, k).
    ``bt`` left ``None`` comes from :func:`row_tile`."""
    from jax.experimental.pallas import tpu as pltpu

    b, n = rows.shape
    k, _ = v.shape
    if bt is None:
        bt = row_tile(b, nt)
    return pl.pallas_call(
        functools.partial(_gather_body, n=n, nt=nt),
        grid=(pl.cdiv(b, bt), pl.cdiv(n, nt)),
        in_specs=[
            pl.BlockSpec((bt, nt), lambda ib, jn: (ib, jn)),
            pl.BlockSpec((k, nt), lambda ib, jn: (0, jn)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda ib, jn: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rows, v.astype(jnp.float32))
