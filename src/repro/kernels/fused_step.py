"""Streaming fused mini-batch step kernels (the `step="fused"` impl).

The composed Algorithm-2 step materializes the (b, k*W) batch x window
cross-kernel strip AND the (b, k) distance matrix in f32 HBM between
kernel launches, so per-step wall clock is bandwidth-bound.  The fused
step streams support tiles through on-chip memory and keeps only
flash-attention-style ONLINE ARGMIN carries — a running best distance and
best center index per batch row — so neither strip ever exists off-chip.

Two implementations, dispatched by :mod:`repro.kernels.ops`:

* ``streaming_assign_pallas`` — the Pallas TPU kernel.  Grid
  ``(b/bt, k, W/st)``: the innermost axis streams (st, d) support tiles
  of one center's window through VMEM, accumulating the coefficient
  contraction into a (bt, 1) VMEM scratch; at the last window tile the
  center's distances fold into the resident best/argmin output blocks.
  VMEM working set per step: bt*d + st*d + bt*st + O(bt) floats — the
  (b, k*W) strip and (b, k) distances never touch HBM.  The whole
  support is read from HBM once per batch tile, so :func:`streaming_tiles`
  makes ``bt`` as large as VMEM allows (the whole padded batch at the
  paper's widths: one sweep of the support per pass).  Mixed precision:
  ``precision="bf16"`` casts the coordinate tiles to bfloat16 before the
  MXU matmul; the cross products, kernel elementwise math, coefficient
  contraction and argmin carries all stay f32 (the Schwartzman'23 regime:
  low-precision evals, full-precision accumulation).

* ``streaming_assign_xla`` / ``streaming_dists_xla`` /
  ``streaming_min_xla`` — the structural XLA fallback used on non-TPU
  backends (and for kernels without an MXU form, e.g. Laplacian or the
  index-data cached kernels).  An UNROLLED loop over center chunks runs
  exactly the composed path's per-chunk ops (same ``kernel_cross`` +
  einsum + distance expression) and folds each chunk into the running
  best/argmin.  Because every chunk repeats the composed arithmetic on a
  >= 2-center slab (1-center slabs change XLA's gemm lowering), the
  result is BIT-IDENTICAL to the composed step at f32 — the equivalence
  the grid sweep in tests/test_api_grid.py pins — while never holding
  more than one (b, kc*W) slab live.  The precondition is that XLA's
  gemm rounds a slab's columns as it does inside the full strip.  On the
  CPU backend that holds when the gemms' column counts are multiples of
  64; at other window widths a column's rounding depends on the gemm's
  total width, and the slab distances differ from the strip's by at most
  2 ulp (tests/test_fused_step.py pins both cases).

Tile choice and the per-backend tuning story live in docs/perf.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernel_fns import KernelFn, is_index_data, kernel_cross
from repro.core.loop import scope
from repro.kernels.fused_assign import _apply_kernel

# Center-chunk width of the XLA fallback: one (b, kc*W) slab live at a
# time.  Chunks are never narrower than 2 centers — XLA lowers a
# single-center (b, W) gemm differently from a k-center slab, which would
# break bit-identity with the composed path (measured, not hypothetical).
STREAM_CHUNK = 8
_MIN_CHUNK = 2


def center_chunks(k: int, kc: int = STREAM_CHUNK):
    """Static (start, width) chunking of k centers with no width-1 chunk
    (a trailing remainder of 1 is merged into the previous chunk)."""
    kc = max(kc, _MIN_CHUNK)
    if k <= kc:
        return [(0, k)]
    chunks = []
    j0 = 0
    while j0 < k:
        kk = min(kc, k - j0)
        if k - (j0 + kk) == 1:          # never leave a width-1 remainder
            kk += 1
        chunks.append((j0, kk))
        j0 += kk
    return chunks


def _precision_cast(kernel: KernelFn, precision: str):
    """Coordinate cast applied before kernel evaluation.  bf16 only ever
    touches COORDINATES: index-data kernels (Precomputed / CachedKernel)
    carry row ids as data, which a cast would corrupt, so they always
    evaluate at full precision."""
    if precision in ("f32", "float32") or is_index_data(kernel):
        return lambda a: a
    if precision in ("bf16", "bfloat16"):
        return lambda a: a.astype(jnp.bfloat16)
    raise ValueError(f"precision={precision!r} (expected 'f32' or 'bf16')")


def _chunk_dists(kernel, cast, xb, sup, coef, sqnorm, diag_b, j0, kk):
    """The composed path's distance block for centers [j0, j0+kk): the
    exact op sequence of ``minibatch._batch_center_dots`` + the distance
    expression, restricted to a center slab."""
    b = xb.shape[0]
    k, w = coef.shape
    sup_c = sup.reshape(k, w, sup.shape[-1])[j0:j0 + kk].reshape(kk * w, -1)
    cross = kernel_cross(kernel, cast(xb), cast(sup_c)).astype(jnp.float32)
    p = jnp.einsum("bkw,kw->bk", cross.reshape(b, kk, w), coef[j0:j0 + kk])
    return diag_b[:, None] - 2.0 * p + sqnorm[None, j0:j0 + kk]


def streaming_assign_xla(kernel: KernelFn, xb: jax.Array, sup: jax.Array,
                         coef: jax.Array, sqnorm: jax.Array,
                         diag_b: jax.Array, *, kc: int = STREAM_CHUNK,
                         precision: str = "f32"):
    """(best, assign): running min distance (b,) f32 and argmin center
    (b,) int32 over all k centers, one (b, kc*W) slab at a time.

    ``lax.optimization_barrier`` threads the batch through the carry
    between slabs: without it XLA's scheduler hoists every slab's gemm
    ahead of the min chain (the slabs have no data dependence on each
    other), which re-materializes the full strip and erases the streaming
    memory win.  The barrier is identity on values, so bit-identity with
    the composed path is untouched."""
    k, _ = coef.shape
    cast = _precision_cast(kernel, precision)
    best = bidx = None
    for j0, kk in center_chunks(k, kc):
        dd = _chunk_dists(kernel, cast, xb, sup, coef, sqnorm, diag_b,
                          j0, kk)
        cmin = jnp.min(dd, axis=1)
        cidx = jnp.argmin(dd, axis=1).astype(jnp.int32) + j0
        if best is None:
            best, bidx = cmin, cidx
        else:
            upd = cmin < best                  # strict: first-min ties,
            best = jnp.where(upd, cmin, best)  # same as jnp.argmin's
            bidx = jnp.where(upd, cidx, bidx)
        best, bidx, xb = jax.lax.optimization_barrier((best, bidx, xb))
    return best, bidx


def streaming_min_xla(kernel: KernelFn, xb: jax.Array, sup: jax.Array,
                      coef: jax.Array, sqnorm: jax.Array,
                      diag_b: jax.Array, *, kc: int = STREAM_CHUNK,
                      precision: str = "f32") -> jax.Array:
    """Running min distance only — the post-update objective pass."""
    k, _ = coef.shape
    cast = _precision_cast(kernel, precision)
    best = None
    for j0, kk in center_chunks(k, kc):
        dd = _chunk_dists(kernel, cast, xb, sup, coef, sqnorm, diag_b,
                          j0, kk)
        cmin = jnp.min(dd, axis=1)
        best = cmin if best is None else jnp.minimum(best, cmin)
        best, xb = jax.lax.optimization_barrier((best, xb))
    return best


def streaming_dists_xla(kernel: KernelFn, xb: jax.Array, sup: jax.Array,
                        coef: jax.Array, sqnorm: jax.Array,
                        diag_b: jax.Array, *, kc: int = STREAM_CHUNK,
                        precision: str = "f32") -> jax.Array:
    """Full (b, k) distance block, computed slab-by-slab.  The sharded
    local step needs the materialized block for its model-axis all_gather
    — (b_loc, k_loc) is small; the win is never holding the (b_loc,
    k_loc*W) strip.  The same barrier chain as
    :func:`streaming_assign_xla` keeps the slabs sequential."""
    k, _ = coef.shape
    cast = _precision_cast(kernel, precision)
    out = []
    for j0, kk in center_chunks(k, kc):
        dd = _chunk_dists(kernel, cast, xb, sup, coef, sqnorm, diag_b,
                          j0, kk)
        dd, xb = jax.lax.optimization_barrier((dd, xb))
        out.append(dd)
    return jnp.concatenate(out, axis=1)


def streamed_sqnorm(kernel: KernelFn, x: jax.Array, idx: jax.Array,
                    coef: jax.Array, *, kc: int = STREAM_CHUNK,
                    compute_dtype=None) -> jax.Array:
    """<C_j, C_j> recompute over INDEX windows, center-chunked and
    barrier-chained: per-center op sequence identical to
    ``minibatch._sqnorm_recompute`` (bit-identical results), but only one
    (kc, W, W) Gram slab is ever live instead of the full (k, W, W) stack
    — at production shapes this is the step's LARGEST allocation, so
    streaming it is what actually lowers the fused step's peak memory.
    Callers must route gram_rows-capable kernels to the composed
    recompute instead (one bulk row lookup beats per-chunk lookups)."""
    k = idx.shape[0]

    def one(idx_row, coef_row):
        pts = x[idx_row]                                       # (W, d)
        if compute_dtype is not None:
            pts = pts.astype(compute_dtype)
        g = kernel_cross(kernel, pts, pts)                     # (W, W)
        if compute_dtype is not None:
            g = g.astype(jnp.float32)
        return coef_row @ (g @ coef_row)

    outs = []
    for j0, kk in center_chunks(k, kc):
        o = jax.vmap(one)(idx[j0:j0 + kk], coef[j0:j0 + kk])
        o, x = jax.lax.optimization_barrier((o, x))
        outs.append(o)
    return jnp.concatenate(outs)


def streamed_sqnorm_pts(kernel: KernelFn, pts: jax.Array, coef: jax.Array,
                        *, kc: int = STREAM_CHUNK,
                        compute_dtype=None) -> jax.Array:
    """:func:`streamed_sqnorm` over COORDINATE windows (k, W, d) — the
    sharded step's layout; per-center ops identical to the paper-faithful
    branch of ``distributed._make_local_step``."""
    k = pts.shape[0]

    def one(pts_row, coef_row):
        p = pts_row if compute_dtype is None \
            else pts_row.astype(compute_dtype)
        g = kernel_cross(kernel, p, p)
        return coef_row @ (g.astype(jnp.float32) @ coef_row)

    outs = []
    for j0, kk in center_chunks(k, kc):
        o = jax.vmap(one)(pts[j0:j0 + kk], coef[j0:j0 + kk])
        o, pts = jax.lax.optimization_barrier((o, pts))
        outs.append(o)
    return jnp.concatenate(outs)


# ---------------------------------------------------------------- Pallas
# Mosaic gives a kernel 16 MiB of scoped VMEM unless it asks for more
# (v5e); the tiles may plan for up to _VMEM_BUDGET of the 128 MiB a v5e
# core holds.
_SCOPED_VMEM = 16 * 2 ** 20
_VMEM_BUDGET = 64 * 2 ** 20
_SUPPORT_TILES = (256, 128)           # widest first
_LANE = 128

# (b, w, d) -> (bt, st, sweeps) of every plan the kernel traced; written
# at trace time only
_STREAMING_PLANS: dict = {}


def streaming_plans() -> dict:
    """Every distinct tile plan the streaming kernel has traced since
    import, ``(b, w, d) -> (bt, st, sweeps)``; ``sweeps`` is how many
    times one pass reads the whole support from HBM."""
    return dict(_STREAMING_PLANS)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(bt: int, st: int, dd: int, itemsize: int) -> int:
    """VMEM of one grid step, counted high: double-buffered x and
    support blocks, their copies cast for the MXU, the (bt, 1) column
    blocks and accumulator (each a lane-padded (bt, 128) f32 tile), the
    (1, st) row blocks (sublane-padded to 8) and three (bt, st) f32
    intermediates (cross products, kernel values, weighted values).
    Compiled for a v5e, (2048, 256) at d=784 needs a scoped limit of
    about 6 MiB against this 34.7 MiB; (8192, 128) at d=64 needs more
    than the 16 MiB default."""
    blocks = 2 * (bt + st) * dd * itemsize + (bt + st) * dd * 2
    cols = (2 * 4 + 1) * bt * _LANE * 4
    rows = 2 * 2 * 8 * st * 4
    return blocks + cols + rows + 3 * bt * st * 4


def streaming_tiles(b: int, w: int, d: int, itemsize: int = 4):
    """-> (bt, st, sweeps, vmem_bytes) for a (b, d) batch against
    windows of w support rows.

    The support is read from HBM once per batch tile, so ``bt`` takes
    the whole padded batch where the working set fits _VMEM_BUDGET,
    else the fewest tiles that do.  The padded batch is never longer
    than the 128-row round-up, and ``st`` never pads w past its 128
    round-up.  Where no tiling fits, the 128-row tiles remain."""
    sub = 8 * 4 // min(itemsize, 4)   # sublanes of one packed VMEM tile
    dd = _round_up(d, _LANE)
    b128 = _round_up(b, _LANE)
    sts = [t for t in _SUPPORT_TILES
           if _round_up(w, t) == _round_up(w, _LANE)]
    for n in range(1, b128 // _LANE + 1):
        bt = _round_up(-(-b // n), sub)
        if n * bt > b128:
            continue
        for st in sts:
            vmem = _vmem_bytes(bt, st, dd, itemsize)
            if vmem <= _VMEM_BUDGET:
                return bt, st, -(-b // bt), vmem
    bt = min(_LANE, _round_up(b, sub))
    return bt, _LANE, -(-b // bt), _vmem_bytes(bt, _LANE, dd, itemsize)


def _stream_body(sqn_ref, x_ref, xsq_ref, diag_ref, sup_ref, supsq_ref,
                 coef_ref, best_ref, idx_ref, p_acc, *, kind, p0, p1, p2,
                 bf16):
    j = pl.program_id(1)
    iw = pl.program_id(2)
    nw = pl.num_programs(2)

    @pl.when(iw == 0)
    def _init_acc():
        p_acc[...] = jnp.zeros_like(p_acc)

    x = x_ref[...]
    s = sup_ref[...]
    if bf16:
        x = x.astype(jnp.bfloat16)
        s = s.astype(jnp.bfloat16)
    xy = jax.lax.dot_general(x, s, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    kv = _apply_kernel(xy, xsq_ref[...], supsq_ref[...], kind, p0, p1, p2)
    p_acc[...] += jnp.sum(kv * coef_ref[...], axis=1, keepdims=True)

    @pl.when(iw == nw - 1)
    def _fold():
        d = diag_ref[...] - 2.0 * p_acc[...] + sqn_ref[j]
        first = j == 0
        prev = jnp.where(first, jnp.full_like(d, jnp.inf), best_ref[...])
        prev_i = jnp.where(first, jnp.zeros_like(idx_ref[...]),
                           idx_ref[...])
        upd = d < prev
        best_ref[...] = jnp.where(upd, d, prev)
        idx_ref[...] = jnp.where(upd, jnp.full_like(prev_i, j), prev_i)


@functools.partial(jax.jit, static_argnames=(
    "kind", "p0", "p1", "p2", "bt", "st", "bf16", "interpret"))
def streaming_assign_pallas(
        xb: jax.Array, sup: jax.Array, coef: jax.Array, sqnorm: jax.Array,
        diag_b: jax.Array, *, kind: str = "gaussian", p0: float = 1.0,
        p1: float = 1.0, p2: int = 2, bt: int | None = None,
        st: int | None = None, bf16: bool = False,
        interpret: bool = False):
    """xb (b, d); sup (k, W, d); coef (k, W); sqnorm (k,); diag_b (b,)
    -> (best (b,) f32, assign (b,) int32).

    ``bt`` / ``st`` left ``None`` come from :func:`streaming_tiles`.
    b / W / d are padded to tile multiples (zero support points with zero
    coefficients contribute nothing; padded batch rows are sliced off).
    Mosaic layout: per-center rows (support norms, coefficients) are
    (k, 1, W) views read in squeezed (1, st) blocks, per-row vectors are
    (b, 1) columns, and ``sqnorm`` sits whole in SMEM, read as scalars.
    The online-argmin outputs live in (bt, 1) blocks revisited across the
    two innermost grid axes — never written back per center."""
    from jax.experimental.pallas import tpu as pltpu

    b, d = xb.shape
    k, w, _ = sup.shape
    itemsize = max(xb.dtype.itemsize, sup.dtype.itemsize)
    rule_bt, rule_st, _, _ = streaming_tiles(b, w, d, itemsize)
    bt = rule_bt if bt is None else bt
    st = rule_st if st is None else st
    _STREAMING_PLANS[(b, w, d)] = (bt, st, -(-b // bt))
    vmem = _vmem_bytes(bt, st, _round_up(d, _LANE), itemsize)
    bp, wp, dp = -b % bt, -w % st, -d % _LANE
    with scope("kkm.pad"):
        xb_p = jnp.pad(xb, ((0, bp), (0, dp)))
        sup_p = jnp.pad(sup, ((0, 0), (0, wp), (0, dp)))
        coef_p = jnp.pad(coef.astype(jnp.float32),
                         ((0, 0), (0, wp)))[:, None]
        diag_p = jnp.pad(diag_b.astype(jnp.float32), (0, bp))[:, None]
        xsq = jnp.sum(xb_p.astype(jnp.float32) ** 2, axis=-1,
                      keepdims=True)
        supsq = jnp.sum(sup_p.astype(jnp.float32) ** 2, axis=-1)[:, None]

    bb, dd = xb_p.shape
    ww = sup_p.shape[1]
    grid = (bb // bt, k, ww // st)
    col = pl.BlockSpec((bt, 1), lambda ib, j, iw: (ib, 0))
    row = pl.BlockSpec((None, 1, st), lambda ib, j, iw: (j, 0, iw))

    best, idx = pl.pallas_call(
        functools.partial(_stream_body, kind=kind, p0=p0, p1=p1, p2=p2,
                          bf16=bf16),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bt, dd), lambda ib, j, iw: (ib, 0)),
            col,
            col,
            pl.BlockSpec((None, st, dd), lambda ib, j, iw: (j, iw, 0)),
            row,
            row,
        ],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((bb, 1), jnp.float32),
            jax.ShapeDtypeStruct((bb, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > _SCOPED_VMEM else None),
        interpret=interpret,
    )(sqnorm.astype(jnp.float32), xb_p, xsq, diag_p, sup_p, supsq, coef_p)
    return best[:b, 0], idx[:b, 0]
