"""jit'd public wrappers around the Pallas kernels.

Dispatch: on TPU the kernels compile natively; on CPU (this container) they
run in interpret mode, which executes the kernel body in Python — identical
numerics, so tests validate the real tiling logic.  Kernels without an
MXU-friendly form (Laplacian L1, Precomputed gathers) fall back to the XLA
reference path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import (
    Gaussian, KernelFn, Linear, Polynomial,
)
from repro.kernels import fused_step, ref
from repro.kernels.cached_gather import (
    cached_assign_dots_pallas, window_weights,
)
from repro.kernels.fused_assign import fused_batch_center_dots_pallas
from repro.kernels.kernel_matmul import kernel_matmul_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _dispatch(kernel: KernelFn):
    """-> (kind, p0, p1, p2) or None when no Pallas form exists."""
    if isinstance(kernel, Gaussian):
        return "gaussian", float(kernel.kappa), 1.0, 2
    if isinstance(kernel, Linear):
        return "linear", 0.0, 1.0, 2
    if isinstance(kernel, Polynomial):
        return "polynomial", float(kernel.bias), float(kernel.scale), \
            int(kernel.degree)
    return None


def _clamp_tile(tile: int, extent: int, mult: int) -> int:
    """Shrink a tile to the padded extent of a small dimension (rounded up
    to ``mult``).  Per-shard support tiles in the distributed step can be
    far smaller than the 128-default tiles — without clamping, interpret
    mode would pad a (b/D, k/D * W) shard up to a full 128x128 grid cell
    and waste most of the work."""
    return min(tile, max(mult, -(-extent // mult) * mult))


def fused_batch_center_dots(kernel: KernelFn, xb: jax.Array,
                            sup_flat: jax.Array, coef: jax.Array,
                            bt: int = 128, st: int = 128,
                            interpret=None) -> jax.Array:
    """P[i,j] = sum_w coef[j,w] K(xb[i], sup[j,w]);  sup_flat: (k*W, d)."""
    k, w = coef.shape
    sup = sup_flat.reshape(k, w, sup_flat.shape[-1])
    disp = _dispatch(kernel)
    if disp is None:
        return ref.batch_center_dots(kernel, xb, sup, coef)
    kind, p0, p1, p2 = disp
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        # CPU/interpret: no MXU tiling constraints, so fit the tiles to the
        # (possibly per-shard) problem.  TPU keeps the caller's tiles.
        bt = _clamp_tile(bt, xb.shape[0], 8)
        st = _clamp_tile(st, w, 8)
    return fused_batch_center_dots_pallas(
        xb, sup, coef, kind=kind, p0=p0, p1=p1, p2=p2, bt=bt, st=st,
        interpret=interpret)


def cached_assign_dots(rows: jax.Array, sup_ids: jax.Array,
                       coef: jax.Array, bt: int | None = None,
                       nt: int = 512, interpret=None) -> jax.Array:
    """P[i,j] = sum_w coef[j,w] rows[i, sup_ids[j,w]] — the assignment
    contraction over full Gram rows (no kernel evaluations; the
    gather-from-cache tile kernel of the repro.cache subsystem)."""
    return gram_row_dots(rows, window_weights(sup_ids, coef, rows.shape[1]),
                         bt=bt, nt=nt, interpret=interpret)


def gram_row_dots(rows: jax.Array, v: jax.Array, bt: int | None = None,
                  nt: int = 512, interpret=None) -> jax.Array:
    """rows @ v^T, (b, k), for Gram rows (b, n) and window weights
    v (k, n) (:func:`repro.kernels.cached_gather.window_weights`), on the
    MXU at ``Precision.HIGHEST``."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        bt = _clamp_tile(bt or 128, rows.shape[0], 8)
        nt = _clamp_tile(nt, rows.shape[1], 8)
    return cached_assign_dots_pallas(rows, v, bt=bt, nt=nt,
                                     interpret=interpret)


def native() -> bool:
    """Whether the Pallas kernels compile natively on this backend (a
    TPU).  Paths with an XLA twin take the twin everywhere else."""
    return not _interpret_default()


def _streaming_dispatch(kernel: KernelFn, interpret):
    """(disp, interpret): the streaming kernels run the Pallas form only
    on TPU for MXU-friendly kernels; everywhere else (CPU CI, Laplacian,
    index-data kernels) the structural XLA fallback runs — it is the
    bit-identical-at-f32 twin of the composed step, which interpret-mode
    Pallas (per-grid-cell emulation) is not."""
    if interpret is None:
        interpret = _interpret_default()
    return _dispatch(kernel), interpret


def streaming_assign(kernel: KernelFn, xb: jax.Array, sup_flat: jax.Array,
                     coef: jax.Array, sqnorm: jax.Array,
                     diag_b: jax.Array, *, precision: str = "f32",
                     bt: int | None = None, st: int | None = None,
                     kc: int = fused_step.STREAM_CHUNK,
                     interpret=None):
    """Streaming fused assignment: (best_dist (b,), assign (b,) int32)
    over all k centers without materializing the (b, k*W) cross strip or
    the (b, k) distances — the `step="fused"` hot pass.
    ``sup_flat``: (k*W, d) support rows (index-data rows for cached /
    precomputed kernels).  Tiles left ``None`` come from
    :func:`fused_step.streaming_tiles`."""
    k, w = coef.shape
    sup = sup_flat.reshape(k, w, sup_flat.shape[-1])
    disp, interpret = _streaming_dispatch(kernel, interpret)
    if disp is None or interpret:
        return fused_step.streaming_assign_xla(
            kernel, xb, sup_flat, coef, sqnorm, diag_b, kc=kc,
            precision=precision)
    kind, p0, p1, p2 = disp
    return fused_step.streaming_assign_pallas(
        xb, sup, coef, sqnorm, diag_b, kind=kind, p0=p0, p1=p1, p2=p2,
        bt=bt, st=st, bf16=precision in ("bf16", "bfloat16"),
        interpret=False)


def streaming_min(kernel: KernelFn, xb: jax.Array, sup_flat: jax.Array,
                  coef: jax.Array, sqnorm: jax.Array, diag_b: jax.Array,
                  *, precision: str = "f32", bt: int | None = None,
                  st: int | None = None,
                  kc: int = fused_step.STREAM_CHUNK, interpret=None):
    """Streaming min distance (b,) only — the fused step's post-update
    objective pass (assignment indices not needed)."""
    disp, interpret = _streaming_dispatch(kernel, interpret)
    if disp is None or interpret:
        return fused_step.streaming_min_xla(
            kernel, xb, sup_flat, coef, sqnorm, diag_b, kc=kc,
            precision=precision)
    k, w = coef.shape
    kind, p0, p1, p2 = disp
    best, _ = fused_step.streaming_assign_pallas(
        xb, sup_flat.reshape(k, w, sup_flat.shape[-1]), coef, sqnorm,
        diag_b, kind=kind, p0=p0, p1=p1, p2=p2, bt=bt, st=st,
        bf16=precision in ("bf16", "bfloat16"), interpret=False)
    return best


def streaming_dists(kernel: KernelFn, xb: jax.Array, sup_flat: jax.Array,
                    coef: jax.Array, sqnorm: jax.Array, diag_b: jax.Array,
                    *, precision: str = "f32", bt: int = 128,
                    st: int = 128, kc: int = fused_step.STREAM_CHUNK,
                    interpret=None) -> jax.Array:
    """Full (b, k) distance block without the (b, k*W) strip — the fused
    SHARDED step's assignment pass (the model-axis all_gather needs the
    materialized per-local-center block).  On TPU the per-center dots run
    through the fused Pallas contraction; elsewhere the slab fallback."""
    disp, interpret = _streaming_dispatch(kernel, interpret)
    if disp is None or interpret:
        return fused_step.streaming_dists_xla(
            kernel, xb, sup_flat, coef, sqnorm, diag_b, kc=kc,
            precision=precision)
    cdt = jnp.bfloat16 if precision in ("bf16", "bfloat16") else None
    xbc = xb.astype(cdt) if cdt is not None else xb
    supc = sup_flat.astype(cdt) if cdt is not None else sup_flat
    p = fused_batch_center_dots(kernel, xbc, supc, coef, bt=bt, st=st,
                                interpret=False)
    return diag_b[:, None].astype(jnp.float32) - 2.0 * p + sqnorm[None, :]


def kernel_matmul(kernel: KernelFn, x: jax.Array, y: jax.Array,
                  v: jax.Array, nt: int = 128, mt: int = 128,
                  interpret=None) -> jax.Array:
    """(K(x, y) @ v) without materializing K."""
    disp = _dispatch(kernel)
    if disp is None:
        return ref.kernel_matmul(kernel, x, y, v)
    kind, p0, p1, p2 = disp
    if interpret is None:
        interpret = _interpret_default()
    return kernel_matmul_pallas(x, y, v, kind=kind, p0=p0, p1=p1, p2=p2,
                                nt=nt, mt=mt, interpret=interpret)
