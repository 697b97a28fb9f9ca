"""``compress_state`` — project truncated-center windows onto m landmark
rows, in place, with an objective-drift certificate.

Each center C_j = sum_w coef[j,w] phi(p_jw) is replaced by its ORTHOGONAL
projection onto the span of m landmarks selected from its own window:

    beta_j = K_mm^{-1} K_mW coef_j          (repro.landmark.basis solve)
    C~_j   = sum_i beta_ji phi(z_ji)

Because the update is a projection, delta_j = C_j - C~_j is orthogonal to
the landmark span, so ||C~_j||^2 = ||C_j||^2 - ||delta_j||^2 and for any
query point (gamma = max ||phi(x)||, 1 for normalized kernels):

    |d(x, C~_j) - d(x, C_j)| <= 2 gamma eps_j + eps_j^2,
    eps_j = ||delta_j||                                (docs/compression.md)

The per-call drift bound reported in :class:`CompressInfo` is the max of
that expression over centers; it bounds the batch-objective drift of ONE
compression and does not compound across cycles (each cycle projects the
CURRENT centers, and the fit between cycles re-descends the objective).

The op is shape-preserving: the (k, W) window arrays keep their shapes
with the first m slots holding the landmarks and the rest zeroed (the
``coef == 0`` empty-slot convention), and the ring head resets to m — so
the SAME compiled Algorithm-2 step keeps running afterwards, which is what
lets every executor trigger compression inside its loop.  The cadence
hook registers ONCE, in the fit-loop core —
:func:`repro.core.loop.compress_hook` wraps ``wrap_step`` /
``wrap_local_step`` below for both the single-device and the shard-local
step bodies (docs/architecture.md); executors opt in through their
``LoopSpec`` hooks rather than wiring the cadence themselves.
"""
from __future__ import annotations

from typing import Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import (
    KernelFn, diag_of, gram_rows_fn, kernel_cross,
)
from repro.core.state import CenterState
from repro.landmark.basis import _SELECTORS, jittered_solve, select_rows

_KEY_SALT = 0x6C4D   # 'lm' — the in-loop selection key namespace


class CompressSpec(NamedTuple):
    """Static (hashable) compression parameters — rides ``MBConfig`` into
    the program-cache keys, so compressed and uncompressed programs never
    collide.  ``every=0`` disables the in-loop hook (round-cadence /
    explicit compression only)."""

    every: int = 0
    m: int = 64
    selector: str = "uniform"
    jitter: float = 1e-6


class CompressInfo(NamedTuple):
    residual: jax.Array       # (k,) ||C_j - C~_j||^2  (projection residual)
    sqnorm_before: jax.Array  # (k,)
    sqnorm_after: jax.Array   # (k,)
    drift_bound: jax.Array    # ()  max_j 2 gamma eps_j + eps_j^2


def spec_of(compress) -> Optional[CompressSpec]:
    """Normalize the ``SolverConfig.compress`` axis value — ``"off"`` /
    ``None``, a mapping, or a (possibly JSON-round-tripped) sequence of
    pairs — to a :class:`CompressSpec` (or ``None`` for off)."""
    if compress is None or compress == "off" or compress == ():
        return None
    if isinstance(compress, CompressSpec):
        d = compress._asdict()
    elif isinstance(compress, Mapping):
        d = dict(compress)
    else:
        try:
            d = {str(key): val for key, val in compress}
        except (TypeError, ValueError):
            raise ValueError(
                f"compress={compress!r}: expected 'off', a mapping like "
                "{'every': T, 'm': m, 'selector': ...}, or a sequence of "
                "pairs") from None
    unknown = set(d) - set(CompressSpec._fields)
    if unknown:
        raise ValueError(f"compress: unknown keys {sorted(unknown)} "
                         f"(expected {CompressSpec._fields})")
    if "m" not in d:
        raise ValueError("compress needs 'm' (the landmark count)")
    spec = CompressSpec(every=int(d.get("every", 0)), m=int(d["m"]),
                        selector=str(d.get("selector", "uniform")),
                        jitter=float(d.get("jitter", 1e-6)))
    if spec.m < 1:
        raise ValueError(f"compress m={spec.m} must be >= 1")
    if spec.every < 0:
        raise ValueError(f"compress every={spec.every} must be >= 0")
    if spec.selector not in _SELECTORS:
        raise ValueError(f"compress selector={spec.selector!r} not in "
                         f"{_SELECTORS}")
    if spec.jitter <= 0:
        raise ValueError("compress jitter must be > 0")
    return spec


def _center_keys(step: jax.Array, k: int, offset) -> jax.Array:
    """Per-center selection keys, pure in ``(step, global center id)`` —
    deterministic across resume/replay (bit-identical crash recovery) and
    decorrelated across model shards via ``offset``."""
    base = jax.random.fold_in(jax.random.PRNGKey(_KEY_SALT), step)
    return jax.vmap(lambda j: jax.random.fold_in(base, j))(
        jnp.arange(k, dtype=jnp.int32) + offset)


def compress_windows(kernel: KernelFn, pts: jax.Array, coef: jax.Array,
                     sqnorm: jax.Array, step: jax.Array,
                     spec: CompressSpec, offset=0):
    """The shared per-center projection over (k, W, d) window points
    (coordinates, or (k, W, 1) index data for cached/precomputed kernels).
    Returns ``(sel (k, m), beta (k, m), new_sqnorm (k,), CompressInfo)``.

    Kernels advertising ``gram_rows`` (the tile cache) resolve ALL k*W
    support strips in ONE lookup outside the per-center vmap — K_mW and
    K_mm then assemble as pure gathers from resident Gram strips (the
    ``cache/`` reuse path; a lookup under vmap would lower its cond to
    select and recompute strips on every hit)."""
    k, w = coef.shape
    m = spec.m
    if m > w:
        raise ValueError(f"compress m={m} exceeds window W={w} "
                         "(m <= tau + batch_size)")
    keys = _center_keys(step, k, offset)
    rows_fn = gram_rows_fn(kernel)
    grams = None
    if rows_fn is not None:
        from repro.cache.cached_kernel import window_grams
        grams = window_grams(kernel, pts)                      # (k, W, W)
    need_gram = spec.selector == "leverage"

    def one(key_j, pts_j, coef_j, sq_j, gram_j):
        mask = coef_j != 0
        sel = select_rows(key_j, gram_j, mask, m, spec.selector,
                          spec.jitter)
        if gram_j is not None:
            kmw = gram_j[sel]                                  # (m, W)
        else:
            kmw = kernel_cross(kernel, pts_j[sel], pts_j) \
                .astype(jnp.float32)
        # Mask empty window slots on BOTH sides: columns so they don't feed
        # the projection, rows so filler landmarks (selected when fewer than
        # m slots are active) stay inert — the jittered diagonal then pins
        # their beta at exactly 0, preserving the coef==0 slot convention.
        kmw = kmw * (mask[sel][:, None] & mask[None, :]).astype(jnp.float32)
        kmm = kmw[:, sel]
        rhs = kmw @ coef_j.astype(jnp.float32)
        beta = jittered_solve(kmm, rhs, spec.jitter)
        csq = beta @ (kmm @ beta)
        resid = jax.nn.relu(sq_j - 2.0 * (beta @ rhs) + csq)
        return sel, beta, csq, resid

    if grams is None and need_gram:
        grams = jax.vmap(
            lambda p: kernel_cross(kernel, p, p).astype(jnp.float32))(pts)
    if grams is not None:
        sel, beta, csq, resid = jax.vmap(one)(keys, pts, coef, sqnorm,
                                              grams)
    else:
        sel, beta, csq, resid = jax.vmap(
            lambda kj, pj, cj, sj: one(kj, pj, cj, sj, None))(
            keys, pts, coef, sqnorm)

    gamma = jnp.sqrt(jnp.maximum(
        jnp.max(diag_of(kernel, pts.reshape(k * w, -1))), 0.0))
    eps = jnp.sqrt(resid)
    info = CompressInfo(residual=resid, sqnorm_before=sqnorm,
                        sqnorm_after=csq,
                        drift_bound=jnp.max(2.0 * gamma * eps + resid))
    return sel, beta, csq, info


def compress_center_state(kernel: KernelFn, state: CenterState,
                          x: jax.Array, spec: CompressSpec, offset=0):
    """Project a :class:`CenterState` onto m landmark rows drawn from its
    own support — shape-preserving (see module docstring).  ``x`` is the
    dataset the window indices point into (the index-data view for
    cached/precomputed kernels).  Returns ``(state', CompressInfo)``."""
    k, w = state.idx.shape
    pts = x[state.idx.reshape(-1)].reshape(k, w, -1)
    sel, beta, csq, info = compress_windows(kernel, pts, state.coef,
                                            state.sqnorm, state.step,
                                            spec, offset)
    lm_idx = jnp.take_along_axis(state.idx, sel, axis=1)       # (k, m)
    new_idx = jnp.zeros_like(state.idx).at[:, :spec.m].set(lm_idx)
    new_coef = jnp.zeros_like(state.coef).at[:, :spec.m].set(beta)
    head = jnp.full_like(state.head, spec.m % w)
    return state._replace(idx=new_idx, coef=new_coef, head=head,
                          sqnorm=csq), info


def compress_dist_state(kernel: KernelFn, state, spec: CompressSpec,
                        offset=0):
    """:func:`compress_center_state` for the sharded coordinate-window (or
    index-window) ``DistState`` — fully center-local, so it runs inside
    the model-sharded ``shard_map`` body with zero collectives."""
    k, w, _ = state.pts.shape
    sel, beta, csq, info = compress_windows(kernel, state.pts, state.coef,
                                            state.sqnorm, state.step,
                                            spec, offset)
    lm = jnp.take_along_axis(state.pts, sel[..., None], axis=1)
    new_pts = jnp.zeros_like(state.pts).at[:, :spec.m].set(lm)
    new_coef = jnp.zeros_like(state.coef).at[:, :spec.m].set(beta)
    head = jnp.full_like(state.head, spec.m % w)
    return state._replace(pts=new_pts, coef=new_coef, head=head,
                          sqnorm=csq), info


def compress_state(kernel: KernelFn, state, compress, x=None):
    """Dispatching front door: compress any supported center-support
    representation (``CenterState`` — needs ``x`` — or ``DistState``)
    onto m landmark rows.  ``compress`` is anything :func:`spec_of`
    accepts.  Returns ``(state', CompressInfo)``."""
    spec = spec_of(compress)
    if spec is None:
        raise ValueError("compress_state called with compress='off'")
    if isinstance(state, CenterState):
        if x is None:
            raise ValueError("CenterState compression needs the dataset x "
                             "its window indices point into")
        return compress_center_state(kernel, state, x, spec)
    if hasattr(state, "pts"):
        return compress_dist_state(kernel, state, spec)
    raise TypeError(f"cannot compress state of type {type(state).__name__}")


# ----------------------------------------------------------- in-loop hooks
def wrap_step(step, kernel: KernelFn, spec: CompressSpec):
    """Wrap a ``make_step`` step so every ``spec.every``-th iteration ends
    with an in-place landmark projection — same (state, x, batch_idx)
    signature and state shapes, so jit/while_loop/donation all carry over.
    (Under a vmapped driver — the multi-restart engine — the ``cond``
    lowers to ``select`` and the projection is computed every step and
    discarded off-cadence; correct, just not free.)"""

    def step2(state, x, batch_idx):
        state, info = step(state, x, batch_idx)
        state = jax.lax.cond(
            (state.step % spec.every) == 0,
            lambda s: compress_center_state(kernel, s, x, spec)[0],
            lambda s: s, state)
        return state, info

    return step2


def wrap_local_step(local_step, kernel: KernelFn, spec: CompressSpec,
                    model_axis: str):
    """The sharded counterpart of :func:`wrap_step` — wraps the
    shard-local Algorithm-2 body; centers are model-sharded, so the
    projection is device-local (selection keys fold in the GLOBAL center
    id via the model-axis index)."""

    def step2(state, xb_loc, w_loc=None, b_eff=None):
        state, info = local_step(state, xb_loc, w_loc=w_loc, b_eff=b_eff)
        k_loc = state.coef.shape[0]
        offset = jax.lax.axis_index(model_axis) * k_loc
        state = jax.lax.cond(
            (state.step % spec.every) == 0,
            lambda s: compress_dist_state(kernel, s, spec,
                                          offset=offset)[0],
            lambda s: s, state)
        return state, info

    return step2


# ------------------------------------------------------- unbounded windows
def grow_window(state: CenterState, extra: int) -> CenterState:
    """Widen the ring window by ``extra`` empty slots (inserted at the
    write head, preserving ring order) — the no-eviction "unbounded
    stream" mode: nothing is ever truncated, so serving cost grows
    linearly with fit history.  This is the baseline the ``compress``
    axis bounds (``tests/test_landmark.py``); the Algorithm-2 step
    reads W from the state shape, so fitting continues unchanged (at the
    cost of a per-growth recompile)."""
    if extra <= 0:
        return state
    k, w = state.idx.shape
    pos = jnp.arange(w)

    def one(idx_row, coef_row, h):
        dest = jnp.where(pos < h, pos, pos + extra)
        idx2 = jnp.zeros((w + extra,), idx_row.dtype).at[dest].set(idx_row)
        coef2 = jnp.zeros((w + extra,),
                          coef_row.dtype).at[dest].set(coef_row)
        return idx2, coef2

    idx2, coef2 = jax.vmap(one)(state.idx, state.coef, state.head)
    return state._replace(idx=idx2, coef=coef2)
