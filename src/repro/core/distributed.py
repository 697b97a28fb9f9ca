"""Multi-chip mini-batch kernel k-means (shard_map).

Sharding layout (see DESIGN.md §4):

* **Centers are sharded over the 'model' axis** — each device owns k/m whole
  centers, so the window ring, eviction bookkeeping, <C,C> maintenance and
  the learning-rate state are all device-LOCAL.  (Index-free: the window
  stores point *coordinates*, so no cross-shard dataset gathers ever occur —
  this also lets activations stream in from a co-resident LM, see
  ``cluster_hidden_states``.)
* **The batch is sharded over ('pod', 'data')** — assignment distances are
  computed on local batch rows against local centers.
* **The dataset itself is sharded over the data axes** in the fully
  on-device path (``fit_distributed_jit``): each data shard samples its
  slice of the batch locally, so no host ever materializes the batch.

Collectives per iteration (the roofline collective term):
  1. all_gather over 'model'  of P_partial (b_loc, k_loc)  -> (b_loc, k)
  2. all_gather over ('pod','data') of the batch (b, d) + assignments (b,)
     [needed so center owners can append their assigned points]
  3. psum of (k,)/scalar reductions.

The step is paper-faithful (Algorithm 2 semantics identical to
repro.core.minibatch); tests assert bit-comparable trajectories against the
single-device implementation on a CPU mesh.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api import keys as api_keys
from repro.core.kernel_fns import (
    KernelFn, gram_rows_fn, kernel_cross, kernel_diag,
)
from repro.core.loop import compress_hook, drive_fit_loop, precision_plan
from repro.core.minibatch import MBConfig
from repro.core.rates import get_rate
from repro.core.state import CenterState


class DistState(NamedTuple):
    """All leading-k arrays are sharded over 'model'."""

    pts: jax.Array      # (k, W, d) window point coordinates
    coef: jax.Array     # (k, W)
    head: jax.Array     # (k,)
    sqnorm: jax.Array   # (k,)
    counts: jax.Array   # (k,)
    step: jax.Array     # ()  replicated


class DistInfo(NamedTuple):
    f_before: jax.Array
    f_after: jax.Array
    improvement: jax.Array
    batch_counts: jax.Array  # (k,) sharded like centers


def init_dist_state(center_pts: jax.Array, kernel: KernelFn,
                    window: int) -> DistState:
    """center_pts: (k, d) initial centers (e.g. k-means++ points)."""
    k, d = center_pts.shape
    pts = jnp.zeros((k, window, d), center_pts.dtype).at[:, 0, :].set(center_pts)
    coef = jnp.zeros((k, window), jnp.float32).at[:, 0].set(1.0)
    return DistState(
        pts=pts, coef=coef,
        head=jnp.ones((k,), jnp.int32),
        sqnorm=kernel_diag(kernel, center_pts).astype(jnp.float32),
        counts=jnp.zeros((k,), jnp.float32),
        step=jnp.zeros((), jnp.int32))


def state_shardings(mesh: Mesh, model_axis: str = "model"):
    m = model_axis
    return DistState(
        pts=NamedSharding(mesh, P(m, None, None)),
        coef=NamedSharding(mesh, P(m, None)),
        head=NamedSharding(mesh, P(m)),
        sqnorm=NamedSharding(mesh, P(m)),
        counts=NamedSharding(mesh, P(m)),
        step=NamedSharding(mesh, P()))


def shard_dataset(x: jax.Array, mesh: Mesh,
                  data_axes: Sequence[str] = ("data",)) -> jax.Array:
    """Place the dataset row-sharded over the data axes (replicated over
    'model').  Rows must divide evenly over the data shards — do NOT pad
    with synthetic rows: the on-device sampler (make_dist_sampling_step)
    draws uniformly from each local slice, so pad rows would silently enter
    training batches.  Subsample to a divisible n instead."""
    n_shards = _data_shard_count(mesh, data_axes)
    if x.shape[0] % n_shards:
        raise ValueError(
            f"dataset rows {x.shape[0]} must divide over {n_shards} data "
            f"shards (drop {x.shape[0] % n_shards} rows; naive padding "
            "would leak synthetic points into sampled batches — "
            "repro.api.KernelKMeans pads AND masks the per-shard sampler "
            "automatically via pad_for_mesh + the n_valid sampler bound)")
    return jax.device_put(x, NamedSharding(mesh, P(tuple(data_axes), None)))


def pad_for_mesh(x: jax.Array, mesh: Mesh,
                 data_axes: Sequence[str] = ("data",),
                 fill: float = 0.0, multiple: int = 1):
    """Pad ``x`` with ``fill`` rows to a row count divisible over the data
    shards (and by ``multiple`` — e.g. a Gram cache tile), returning
    ``(x_padded, n_valid)`` where ``n_valid`` is the real row count.  Feed
    ``n_valid`` to :func:`make_dist_sampling_step` /
    :func:`make_cached_dist_sampling_step` so the shard-local samplers mask
    pad rows out — the fill value then never reaches a batch, a window or
    a Gram evaluation (tested for fill-independence).  Pad rows land on the
    trailing data shards; a shard that ends up ALL padding (tiny n relative
    to the shard count, or a large ``multiple``) is zero-weighted out of
    every sampled batch by the step builders, so even then no synthetic
    point is ever trained on."""
    n = x.shape[0]
    n_shards = _data_shard_count(mesh, data_axes)
    pad = (-n) % math.lcm(n_shards, multiple)
    if pad == 0:
        return x, n
    fill_rows = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, fill_rows], axis=0), n


def _data_shard_count(mesh: Mesh, data_axes: Sequence[str]) -> int:
    return int(math.prod(mesh.shape[a] for a in data_axes))


def _replica_index(mesh: Mesh, data_axes: Sequence[str]) -> jax.Array:
    """Flat index of this device among the data replicas (row-major over
    data_axes) — must stay the single source of truth so shard-local batch
    sampling and sharded Gram-row ownership agree."""
    ridx = jnp.zeros((), jnp.int32)
    for ax in data_axes:
        ridx = ridx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return ridx


def _make_local_step(kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                     data_axes: Sequence[str], model_axis: str):
    """The per-device Algorithm-2 iteration body (runs inside shard_map)."""
    if cfg.step not in ("composed", "fused"):
        raise ValueError(f"step={cfg.step!r} (expected 'composed' or "
                         "'fused')")
    if cfg.sqnorm_mode == "recompute_sharded":
        from repro.core.state import window_size
        w = window_size(cfg.batch_size, cfg.tau)
        r = _data_shard_count(mesh, data_axes)
        if w % r:
            raise ValueError(
                f"sqnorm_mode='recompute_sharded' needs window W={w} "
                f"divisible by the {r} data shards (else Gram rows "
                f"{w - w % r}..{w - 1} would be computed by no shard)")
    rate_fn = get_rate(cfg.rate)
    b = cfg.batch_size
    data_axes = tuple(data_axes)

    # index-data kernels carry row ids as data — a precision cast would
    # corrupt the gather keys, and the streaming slab loop would multiply
    # cache lookups for values that are gathers; they keep the composed
    # passes (and full precision) regardless of cfg.step / compute_dtype.
    # The resolution lives ONCE in the loop core (precision_plan).
    prec = precision_plan(kernel, cfg)
    index_data = prec.index_data
    stream = cfg.step == "fused" and not index_data
    cdt = prec.cdt
    _c = prec.cast  # bf16 = MXU native; coefficients/accumulations stay f32

    def p_of(pts, coef, xb_loc):
        """P[i,j] = <phi(xb_loc[i]), C_j> over this shard's centers.

        With ``cfg.use_pallas`` the fused Pallas kernel runs on the
        per-shard support tile (k_loc, W, d) — each device streams only its
        own centers' windows through VMEM, so tiles shrink with the model
        axis and never touch remote support points."""
        k_loc, w, d = pts.shape
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            return kops.fused_batch_center_dots(
                kernel, _c(xb_loc), _c(pts.reshape(k_loc * w, d)), coef)
        cross = kernel_cross(kernel, _c(xb_loc), _c(pts.reshape(k_loc * w, d)))
        return jnp.einsum("bkw,kw->bk",
                          cross.reshape(xb_loc.shape[0], k_loc, w)
                          .astype(jnp.float32), coef)

    def _row_mean(vals_loc, w_loc, b_eff):
        """Mean of a per-local-row quantity over the REAL batch rows.
        ``w_loc=None`` (no fully-padded shard possible) keeps the exact
        historical mean-of-means operation order, so pre-existing
        trajectories stay bit-identical."""
        if w_loc is None:
            m = jnp.mean(vals_loc)
            for ax in data_axes:
                m = jax.lax.pmean(m, ax)
            return m
        m = jnp.sum(vals_loc * w_loc)
        for ax in data_axes:
            m = jax.lax.psum(m, ax)
        return m / b_eff

    def local_step(state: DistState, xb_loc: jax.Array, w_loc=None,
                   b_eff=None):
        """``w_loc``: optional (b_loc,) 0/1 row weights — rows of a fully
        padded data shard carry weight 0 and contribute to NOTHING (no
        window append, no count, no objective term); ``b_eff`` is then the
        real global batch size (static)."""
        k_loc, w, d = state.pts.shape
        m_idx = jax.lax.axis_index(model_axis)
        center_gid0 = m_idx * k_loc  # first global center id on this device

        # ---- assignment: local batch rows x local centers ------------------
        diag_b = kernel_diag(kernel, xb_loc).astype(jnp.float32)   # (b_loc,)
        if stream:
            # streaming per-shard distances: the (b_loc, k_loc) block is
            # required by the model-axis gather below, but the
            # (b_loc, k_loc*W) cross strip never materializes
            from repro.kernels import ops as kops
            d_loc = kops.streaming_dists(
                kernel, xb_loc, state.pts.reshape(k_loc * w, d),
                state.coef, state.sqnorm, diag_b,
                precision="bf16" if cdt is not None else "f32")
        else:
            p_loc = p_of(state.pts, state.coef, xb_loc)        # (b_loc,k_loc)
            d_loc = diag_b[:, None] - 2.0 * p_loc + state.sqnorm[None, :]
        d_all = jax.lax.all_gather(d_loc, model_axis, axis=1, tiled=True)
        f_before = _row_mean(jnp.min(d_all, axis=1), w_loc, b_eff)
        assign_loc = jnp.argmin(d_all, axis=1).astype(jnp.int32)   # global ids

        # ---- gather the full batch so center owners can ingest it ---------
        xb_full, assign, w_full = xb_loc, assign_loc, w_loc
        for ax in reversed(data_axes):
            xb_full = jax.lax.all_gather(xb_full, ax, axis=0, tiled=True)
            assign = jax.lax.all_gather(assign, ax, axis=0, tiled=True)
            if w_full is not None:
                w_full = jax.lax.all_gather(w_full, ax, axis=0, tiled=True)

        onehot_loc = jax.nn.one_hot(assign - center_gid0, k_loc,
                                    dtype=jnp.float32)             # (b, k_loc)
        if w_full is not None:
            onehot_loc = onehot_loc * w_full[:, None]
        bj = jnp.sum(onehot_loc, axis=0)                           # (k_loc,)
        alpha = rate_fn(bj, state.counts, b if w_loc is None else b_eff)
        decay = 1.0 - alpha

        # ---- local ring append --------------------------------------------
        coef_scaled = state.coef * decay[:, None]

        def one_center(pts_row, coef_row, head_j, alpha_j, bj_j, mask_j):
            pos = jnp.cumsum(mask_j.astype(jnp.int32)) - 1
            slot = jnp.where(mask_j, (head_j + pos) % w, w)
            coef_row = coef_row.at[slot].set(
                alpha_j / jnp.maximum(bj_j, 1.0), mode="drop")
            pts_row = pts_row.at[slot].set(xb_full, mode="drop")
            return pts_row, coef_row, (head_j + bj_j.astype(jnp.int32)) % w

        mask = onehot_loc.T.astype(bool)                           # (k_loc, b)
        new_pts, new_coef, new_head = jax.vmap(one_center)(
            state.pts, coef_scaled, state.head, alpha, bj, mask)

        # ---- <C,C> recompute ----------------------------------------------
        if cfg.sqnorm_mode == "recompute_sharded":
            # Beyond-paper (§Perf cell A): the baseline recomputes every
            # center's full W x W Gram on EVERY data-row replica — R-fold
            # redundant.  Here each data row computes W/R Gram rows and the
            # quadratic form is psum'd: per-device flops drop by R.
            r_total = _data_shard_count(mesh, data_axes)
            ridx = _replica_index(mesh, data_axes)
            rows = w // r_total

            def sq_one(pts_row, coef_row):
                sl = jax.lax.dynamic_slice_in_dim(pts_row, ridx * rows,
                                                  rows, 0)
                csl = jax.lax.dynamic_slice_in_dim(coef_row, ridx * rows,
                                                   rows, 0)
                g = kernel_cross(kernel, _c(sl), _c(pts_row))  # (W/R, W)
                return csl @ (g.astype(jnp.float32) @ coef_row)

            part = jax.vmap(sq_one)(new_pts, new_coef)
            new_sqnorm = part
            for ax in data_axes:
                new_sqnorm = jax.lax.psum(new_sqnorm, ax)
        elif gram_rows_fn(kernel) is not None:
            # cached kernel: resolve all local support rows in ONE lookup
            # outside the per-center vmap (a cached lookup under vmap
            # lowers its cond to select and recomputes strips on hits),
            # then gather each center's W x W block
            rows_fn = gram_rows_fn(kernel)
            rows = rows_fn(kernel, new_pts.reshape(k_loc * w, d))
            rows_k = rows.reshape(k_loc, w, rows.shape[-1])
            ids = new_pts[..., 0].astype(jnp.int32)            # (k_loc, W)

            def sq_one(rows_j, ids_j, coef_row):
                g = rows_j[:, ids_j]                           # (W, W)
                return coef_row @ (g.astype(jnp.float32) @ coef_row)

            new_sqnorm = jax.vmap(sq_one)(rows_k, ids, new_coef)
        elif stream:
            # streamed center-chunked recompute (same per-center ops as
            # the composed branch below — bit-identical): only one
            # (kc, W, W) Gram slab live per shard instead of the full
            # (k_loc, W, W) stack
            from repro.kernels.fused_step import streamed_sqnorm_pts
            new_sqnorm = streamed_sqnorm_pts(kernel, new_pts, new_coef,
                                             compute_dtype=cdt)
        else:
            # paper-faithful local Gram per center
            def sq_one(pts_row, coef_row):
                g = kernel_cross(kernel, _c(pts_row), _c(pts_row))
                return coef_row @ (g.astype(jnp.float32) @ coef_row)

            new_sqnorm = jax.vmap(sq_one)(new_pts, new_coef)

        # ---- batch objective on new centers (early stopping) ---------------
        if stream:
            from repro.kernels import ops as kops
            best2 = kops.streaming_min(
                kernel, xb_loc, new_pts.reshape(k_loc * w, d), new_coef,
                new_sqnorm, diag_b,
                precision="bf16" if cdt is not None else "f32")
        else:
            p2 = p_of(new_pts, new_coef, xb_loc)
            d2 = diag_b[:, None] - 2.0 * p2 + new_sqnorm[None, :]
            best2 = jnp.min(d2, axis=1)
        d2_min = jax.lax.pmin(best2, model_axis)                   # (b_loc,)
        f_after = _row_mean(d2_min, w_loc, b_eff)

        new_state = DistState(pts=new_pts, coef=new_coef, head=new_head,
                              sqnorm=new_sqnorm, counts=state.counts + bj,
                              step=state.step + 1)
        return new_state, DistInfo(f_before, f_after, f_before - f_after, bj)

    # in-loop landmark projection of the shard-local center windows
    # (fully center-local — zero collectives); compress=None emits the
    # historical program unchanged.  Single registration site: loop core.
    return compress_hook(local_step, kernel, cfg, local=True,
                         model_axis=model_axis)


def _state_specs(model_axis: str):
    return DistState(
        pts=P(model_axis, None, None), coef=P(model_axis, None),
        head=P(model_axis), sqnorm=P(model_axis), counts=P(model_axis),
        step=P())


def make_dist_step(kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                   data_axes: Sequence[str] = ("data",),
                   model_axis: str = "model"):
    """Returns step(state, xb) -> (state, info), a shard_map'd Algorithm-2
    iteration.  xb: (b, d) batch sharded over data_axes on rows."""
    data_axes = tuple(data_axes)
    local_step = _make_local_step(kernel, cfg, mesh, data_axes, model_axis)
    state_specs = _state_specs(model_axis)
    info_specs = DistInfo(P(), P(), P(), P(model_axis))

    return shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, P(data_axes, None)),
        out_specs=(state_specs, info_specs),
        check_vma=False)


def _local_sample_bound(mesh: Mesh, data_axes: Sequence[str],
                        n_loc: int, n_valid: Optional[int]):
    """``(bound, has_real)`` for this shard's local randint draw.

    ``n_valid=None`` (no padding) keeps the historical static bound — the
    full local slice (``has_real=None``).  With ``n_valid`` set (the real
    global row count of a dataset padded by :func:`pad_for_mesh`), each
    shard samples only its REAL rows: shard s owns padded rows
    [s*L, (s+1)*L), of which ``clip(n_valid - s*L, 0, L)`` are real.  The
    bound is clamped to >= 1 so the draw stays well-formed on a shard that
    is ALL padding; such a shard's ``has_real`` flag is False and the step
    builders zero-weight its rows out of the batch (they never reach a
    window, a count or an objective — the docstring guarantee "pad rows
    are masked out of every batch" holds even then).  Shards with fewer
    real rows oversample them proportionally — an O(pad/n) stratification
    skew, traded for never training on synthetic points."""
    if n_valid is None:
        return n_loc, None
    start = _replica_index(mesh, data_axes) * n_loc
    real = jnp.clip(n_valid - start, 0, n_loc)
    return jnp.maximum(real, 1), real > 0


def _batch_mask(has_real, b_loc: int, n_shards: int, n_loc: int,
                n_valid: int):
    """``(w_loc, b_eff)`` zero-weighting the rows of fully-padded shards:
    shard s has real rows iff s < ceil(n_valid / L), so the effective
    global batch size is static."""
    w_loc = jnp.broadcast_to(has_real.astype(jnp.float32), (b_loc,))
    n_active = min(n_shards, -(-n_valid // n_loc))
    return w_loc, b_loc * n_active


def _make_sampling_body(kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                        data_axes: Sequence[str] = ("data",),
                        model_axis: str = "model",
                        n_valid: Optional[int] = None):
    """The UNWRAPPED shard-local sampled step (state, x_loc, key) ->
    (state, info) — shared by :func:`make_dist_sampling_step` (which
    shard_maps it over a data x model mesh) and the fused restart program
    (:func:`repro.core.engine.make_fused_restart_run`, which runs it per
    restart lane inside a restart x data x model shard_map)."""
    data_axes = tuple(data_axes)
    n_shards = _data_shard_count(mesh, data_axes)
    if cfg.batch_size % n_shards:
        raise ValueError(f"batch_size {cfg.batch_size} must divide over "
                         f"{n_shards} data shards (repro.api.KernelKMeans "
                         "rounds the batch size up automatically)")
    b_loc = cfg.batch_size // n_shards
    local_step = _make_local_step(kernel, cfg, mesh, data_axes, model_axis)

    def sampled(state: DistState, x_loc: jax.Array, key: jax.Array):
        kb = api_keys.shard_key(key, _replica_index(mesh, data_axes))
        n_loc = x_loc.shape[0]
        hi, has_real = _local_sample_bound(mesh, data_axes, n_loc, n_valid)
        bidx = jax.random.randint(kb, (b_loc,), 0, hi, dtype=jnp.int32)
        if n_valid is not None and n_valid <= (n_shards - 1) * n_loc:
            w_loc, b_eff = _batch_mask(has_real, b_loc, n_shards, n_loc,
                                       n_valid)
            return local_step(state, x_loc[bidx], w_loc=w_loc, b_eff=b_eff)
        return local_step(state, x_loc[bidx])

    return sampled


def make_dist_sampling_step(kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                            data_axes: Sequence[str] = ("data",),
                            model_axis: str = "model",
                            n_valid: Optional[int] = None):
    """Returns step(state, x, key) -> (state, info) where x is the FULL
    dataset row-sharded over the data axes and the batch is sampled
    on-device: each data shard draws b / n_shards rows uniformly from its
    local slice (stratified-uniform over equal shards — same marginal as
    the paper's uniform-with-replacement model).

    ``n_valid``: real row count of a :func:`pad_for_mesh`-padded dataset —
    masks pad rows out of the shard-local draws (see
    :func:`_local_sample_bound`); the rows of a shard that is ALL padding
    are zero-weighted out of the batch entirely."""
    data_axes = tuple(data_axes)
    sampled = _make_sampling_body(kernel, cfg, mesh, data_axes, model_axis,
                                  n_valid)
    state_specs = _state_specs(model_axis)
    info_specs = DistInfo(P(), P(), P(), P(model_axis))

    return shard_map(
        sampled, mesh=mesh,
        in_specs=(state_specs, P(data_axes, None), P()),
        out_specs=(state_specs, info_specs),
        check_vma=False)


def _fit_distributed_impl(xb_stream, center_pts: jax.Array,
                          kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                          data_axes: Sequence[str] = ("data",),
                          model_axis: str = "model",
                          early_stop: bool = True,
                          prefetch: bool = False):
    """Stream-driven sharded fit loop (shared by the ``sharded`` host plan
    and :func:`cluster_hidden_states`).

    ``prefetch``: one-deep double buffering — the NEXT batch is pulled
    from the host iterator and its ``device_put`` transfer issued right
    after step i is dispatched, before the loop blocks on step i's
    improvement, so host-to-device transfer overlaps the sharded step
    (the ROADMAP async-prefetch item).  The step consumes the same batch
    values in the same order, so results are bit-identical to the
    blocking path (tested); the only observable difference is that an
    early stop may have consumed one extra item from the iterator.

    Lowered onto the shared host driver
    (:func:`repro.core.loop.drive_fit_loop`): this function supplies only
    the iterator-backed batch producer, the mesh staging (``device_put``
    to the data-axes sharding) and the sharded step dispatch."""
    from repro.core.state import window_size

    w = window_size(cfg.batch_size, cfg.tau)
    state = init_dist_state(center_pts, kernel, w)
    shardings = state_shardings(mesh, model_axis)
    state = jax.device_put(state, shardings)
    step = jax.jit(make_dist_step(kernel, cfg, mesh, data_axes, model_axis),
                   donate_argnums=(0,))
    xspec = NamedSharding(mesh, P(tuple(data_axes), None))

    it = iter(xb_stream)

    def draw(cursor, i):
        # stream-driven: the cursor is unused, the iterator is the state
        return cursor, next(it, None)

    def dispatch(xb):
        nonlocal state
        state, info = step(state, jax.device_put(xb, xspec))
        return info

    history, _ = drive_fit_loop(
        dispatch, draw, None, max_iters=cfg.max_iters, epsilon=cfg.epsilon,
        early_stop=early_stop, prefetch=prefetch,
        stage=lambda xb: jax.device_put(xb, xspec))
    return state, history


def fit_distributed(xb_stream, center_pts: jax.Array, kernel: KernelFn,
                    cfg: MBConfig, mesh: Mesh,
                    data_axes: Sequence[str] = ("data",),
                    model_axis: str = "model",
                    early_stop: bool = True):
    """Drive the sharded step from a host iterator of (b, d) batches —
    this is `cluster_hidden_states` when the iterator yields LM activations.

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(distribution="sharded", jit=False)`` (the estimator
        samples its batches through the unified key stream) — this shim
        resolves exactly that plan and delegates the stream to it.
    """
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.distributed.fit_distributed",
        "KernelKMeans(SolverConfig(distribution='sharded', jit=False))")
    return _legacy.fit_distributed(xb_stream, center_pts, kernel, cfg, mesh,
                                   data_axes=data_axes,
                                   model_axis=model_axis,
                                   early_stop=early_stop)


def fit_distributed_jit(x: jax.Array, center_pts: jax.Array,
                        kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                        key: jax.Array,
                        data_axes: Sequence[str] = ("data",),
                        model_axis: str = "model"):
    """Fully on-device distributed fit: the dataset stays sharded across the
    mesh, batches are sampled shard-locally, and the whole early-stopped loop
    is ONE compiled program — zero per-step host sync (the production path).

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(distribution="sharded", jit=True)`` — this shim
        resolves exactly that plan and delegates to it (the estimator
        additionally pads-and-masks non-divisible datasets and caches the
        compiled program across fits).

    Returns (state, iters) like :func:`repro.core.minibatch.fit_jit`."""
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.distributed.fit_distributed_jit",
        "KernelKMeans(SolverConfig(distribution='sharded', jit=True))")
    return _legacy.fit_distributed_jit(x, center_pts, kernel, cfg, mesh,
                                       key, data_axes=data_axes,
                                       model_axis=model_axis)


# --------------------------------------------------------------------------
# Per-shard Gram tile caches (repro.cache subsystem under the shard_map shim)
#
# In the cached distributed fit the dataset flows as (n, 1) index-data (the
# CachedKernel convention, same as Precomputed), so locally sampled batch
# rows carry their GLOBAL row ids — each data shard warms its own tile
# cache with exactly the blocks its local samples touch ("shard-local
# keys"), and the unchanged local Algorithm-2 step then serves every
# cross-kernel block from resident tiles.  The caches are stacked on a
# leading data-shard axis and ride the while_loop carry, so warmth persists
# across the whole zero-host-sync fit.


def init_shard_caches(mesh: Mesh, n: int, tile: int, capacity: int,
                      data_axes: Sequence[str] = ("data",),
                      dtype=jnp.float32, restarts: Optional[int] = None,
                      restart_axis: str = "restart"):
    """One empty GramTileCache per data shard, stacked on a leading axis
    that is sharded over ``data_axes`` (replicated over 'model' — devices
    along the model axis see the same batch rows, so their cache contents
    evolve identically).

    ``restarts=R`` (the fused restart x data x model plan) prepends a
    restart axis: one cache per (restart, data-shard) pair, leaves stacked
    ``(R, S, ...)`` and sharded ``P(restart_axis, data_axes, ...)`` —
    restarts draw independent batches, so their working sets (and caches)
    evolve independently."""
    from repro.cache import tile_cache

    data_axes = tuple(data_axes)
    s = _data_shard_count(mesh, data_axes)
    c0 = tile_cache.create_cache(n, tile, capacity, dtype)
    lead = (s,) if restarts is None else (restarts, s)
    axes = (data_axes,) if restarts is None else (restart_axis, data_axes)
    stacked = jax.tree.map(
        lambda a: jnp.tile(a[(None,) * len(lead)],
                           lead + (1,) * a.ndim), c0)
    return jax.device_put(stacked, jax.tree.map(
        lambda a: NamedSharding(
            mesh, P(*axes, *([None] * (a.ndim - len(lead))))),
        stacked))


def _make_cached_sampling_body(base_kernel: KernelFn, x_real: jax.Array,
                               cfg: MBConfig, mesh: Mesh,
                               data_axes: Sequence[str] = ("data",),
                               model_axis: str = "model",
                               n_valid: Optional[int] = None):
    """The UNWRAPPED cached shard-local sampled step
    (state, caches_loc, x_loc, key) -> (state, caches_loc, info) — shared
    by :func:`make_cached_dist_sampling_step` and the fused restart
    program.  ``caches_loc`` leaves carry the leading length-1 data-shard
    stacking axis (what shard_map hands a data shard of the
    :func:`init_shard_caches` stack)."""
    from repro.cache import tile_cache
    from repro.cache.cached_kernel import CachedKernel

    if cfg.compute_dtype != "float32":
        raise ValueError("cached distributed fit carries row indices as "
                         "data; compute_dtype casts would corrupt them")
    if cfg.sqnorm_mode != "recompute":
        raise ValueError("cached distributed fit supports sqnorm_mode="
                         "'recompute' (the sharded variant slices window "
                         "rows inside per-center vmaps, which defeats the "
                         "cache's cond-skip)")
    data_axes = tuple(data_axes)
    n_shards = _data_shard_count(mesh, data_axes)
    if cfg.batch_size % n_shards:
        raise ValueError(f"batch_size {cfg.batch_size} must divide over "
                         f"{n_shards} data shards (repro.api.KernelKMeans "
                         "rounds the batch size up automatically)")
    b_loc = cfg.batch_size // n_shards

    def cached_sampled(state: DistState, caches, x_loc: jax.Array,
                       key: jax.Array):
        kb = api_keys.shard_key(key, _replica_index(mesh, data_axes))
        n_loc = x_loc.shape[0]
        hi, has_real = _local_sample_bound(mesh, data_axes, n_loc, n_valid)
        bidx = jax.random.randint(kb, (b_loc,), 0, hi, dtype=jnp.int32)
        xb_loc = x_loc[bidx]                       # (b_loc, 1) global ids
        w_loc = b_eff = None
        if n_valid is not None and n_valid <= (n_shards - 1) * n_loc:
            w_loc, b_eff = _batch_mask(has_real, b_loc, n_shards, n_loc,
                                       n_valid)
            # a fully-padded shard's (zero-weighted) draws point at pad
            # rows — rewrite them to row 0 so the warm set and every
            # cached lookup stay on REAL, resident tiles
            xb_loc = jnp.where(has_real, xb_loc[:, 0],
                               jnp.zeros((), x_loc.dtype))[:, None]
        # Warm set = FULL batch + this shard's current window rows: the
        # local step all_gathers the batch into the center windows, so
        # window rows originate from every data shard — warming only the
        # local slice would leave them missing on each sqnorm recompute.
        ids_full = xb_loc[:, 0].astype(jnp.int32)
        for ax in reversed(data_axes):
            ids_full = jax.lax.all_gather(ids_full, ax, axis=0, tiled=True)
        # windows are model-sharded: gather ALL centers' window ids so the
        # warm set (and thus the cache contents, replicated over 'model')
        # is identical on every device of a data shard
        win_ids = jax.lax.all_gather(
            state.pts[..., 0].reshape(-1).astype(jnp.int32), model_axis,
            axis=0, tiled=True)
        cache = jax.tree.map(lambda a: a[0], caches)
        cache = tile_cache.warm(cache, base_kernel, x_real,
                                jnp.concatenate([ids_full, win_ids]))
        ck = CachedKernel(base=base_kernel, x=x_real, cache=cache)
        local_step = _make_local_step(ck, cfg, mesh, data_axes, model_axis)
        new_state, info = local_step(state, xb_loc, w_loc=w_loc,
                                     b_eff=b_eff)
        return new_state, jax.tree.map(lambda a: a[None], cache), info

    return cached_sampled


def make_cached_dist_sampling_step(base_kernel: KernelFn, x_real: jax.Array,
                                   cfg: MBConfig, mesh: Mesh,
                                   data_axes: Sequence[str] = ("data",),
                                   model_axis: str = "model",
                                   n_valid: Optional[int] = None):
    """Cached variant of :func:`make_dist_sampling_step`: returns
    step(state, caches, x_idx, key) -> (state, caches, info), where x_idx is
    the (n, 1) index-data dataset row-sharded over ``data_axes`` and
    ``caches`` the stacked per-shard tile caches of
    :func:`init_shard_caches`.  ``base_kernel`` / ``x_real`` (the actual
    coordinates) are closed over and replicated."""
    data_axes = tuple(data_axes)
    cached_sampled = _make_cached_sampling_body(
        base_kernel, x_real, cfg, mesh, data_axes, model_axis, n_valid)

    from repro.cache.tile_cache import GramTileCache

    state_specs = _state_specs(model_axis)
    info_specs = DistInfo(P(), P(), P(), P(model_axis))
    # stacked cache ranks: store (S,C,tile,n); keys/stamp (S,C); scalars (S,)
    cache_specs = GramTileCache(
        store=P(data_axes, None, None, None), keys=P(data_axes, None),
        stamp=P(data_axes, None), clock=P(data_axes), hits=P(data_axes),
        misses=P(data_axes), evictions=P(data_axes))

    return shard_map(
        cached_sampled, mesh=mesh,
        in_specs=(state_specs, cache_specs, P(data_axes, None), P()),
        out_specs=(state_specs, cache_specs, info_specs),
        check_vma=False)


def fit_distributed_cached_jit(x: jax.Array, init_idx: jax.Array,
                               base_kernel: KernelFn, cfg: MBConfig,
                               mesh: Mesh, key: jax.Array,
                               tile: int = 256, capacity: int = 16,
                               data_axes: Sequence[str] = ("data",),
                               model_axis: str = "model",
                               cache_dtype=jnp.float32):
    """Cached :func:`fit_distributed_jit`: same fully on-device
    early-stopped loop (one compiled program, zero per-step host sync), but
    every data shard carries a Gram tile cache in the while_loop state —
    repeated rows across sampled batches stop re-evaluating the kernel.

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(distribution="sharded", cache="lru", jit=True)`` —
        this shim resolves exactly that plan and delegates to it.

    ``x``: (n, d) real coordinates; ``init_idx``: (k,) initial center row
    indices.  Sampling is identical to the uncached path (same fold_in /
    randint stream), so trajectories are numerically equivalent.
    Returns (state, caches, iters); ``repro.cache.stats`` on a
    ``jax.tree.map(lambda a: a[s], caches)`` slice reports shard s's
    hit/miss telemetry."""
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.distributed.fit_distributed_cached_jit",
        "KernelKMeans(SolverConfig(distribution='sharded', cache='lru', "
        "jit=True))")
    return _legacy.fit_distributed_cached_jit(
        x, init_idx, base_kernel, cfg, mesh, key, tile=tile,
        capacity=capacity, data_axes=data_axes, model_axis=model_axis,
        cache_dtype=cache_dtype)


def dist_to_center_state(dst: DistState) -> CenterState:
    """View a coordinate-window DistState as an index-free CenterState-like
    tuple for serving: ``idx`` is a placeholder arange since predict paths
    below consume coordinates directly."""
    k, w, _ = dst.pts.shape
    return CenterState(idx=jnp.arange(k * w, dtype=jnp.int32).reshape(k, w),
                       coef=dst.coef, head=dst.head, sqnorm=dst.sqnorm,
                       counts=dst.counts, step=dst.step)


# Compiled serving programs, keyed by everything baked into the closure;
# array shapes/dtypes are handled by each cached function's own jit cache.
_PREDICT_FNS: dict = {}


def _predict_fn(mesh: Mesh, data_axes, treedef, loc_chunk: int):
    key = (mesh, data_axes, treedef, loc_chunk)
    fn = _PREDICT_FNS.get(key)
    if fn is None:
        from repro.core.minibatch import assign_chunked

        def local_predict(kern_leaves, coef, sqnorm, sup, xq_loc):
            kern = jax.tree_util.tree_unflatten(treedef, kern_leaves)
            return assign_chunked(kern, coef, sqnorm, sup, xq_loc,
                                  loc_chunk)

        fn = jax.jit(shard_map(
            local_predict, mesh=mesh,
            in_specs=([P()] * treedef.num_leaves, P(), P(), P(),
                      P(data_axes, None)),
            out_specs=P(data_axes),
            check_vma=False))
        _PREDICT_FNS[key] = fn
    return fn


def predict_distributed(state: CenterState, x: jax.Array, xq: jax.Array,
                        kernel: KernelFn, mesh: Mesh,
                        data_axes: Optional[Sequence[str]] = None,
                        chunk: int = 4096) -> jax.Array:
    """Sharded serving variant of :func:`repro.core.minibatch.predict`:
    query rows are sharded over the mesh's data axes, support windows are
    replicated, and each device classifies its rows with zero collectives
    (the chunked kernel itself is ``minibatch.assign_chunked``, shared with
    the single-device path).  Handles arbitrary (non-divisible) query
    counts by padding.  The compiled program is cached per
    (mesh, axes, kernel structure, chunk) so repeated serving calls don't
    re-trace."""
    if data_axes is None:
        data_axes = tuple(a for a in mesh.axis_names if a != "model")
    data_axes = tuple(data_axes)
    n_shards = _data_shard_count(mesh, data_axes)
    nq = xq.shape[0]
    pad = (-nq) % n_shards
    xq_p = jnp.pad(xq, ((0, pad),) + ((0, 0),) * (xq.ndim - 1))

    sup = x[state.idx.reshape(-1)]                   # (k*W, d) replicated
    loc_chunk = min(chunk, max(xq_p.shape[0] // n_shards, 1))

    leaves, treedef = jax.tree_util.tree_flatten(kernel)
    fn = _predict_fn(mesh, data_axes, treedef, loc_chunk)
    xq_sh = jax.device_put(xq_p, NamedSharding(mesh, P(data_axes, None)))
    out = fn(leaves, state.coef, state.sqnorm, sup, xq_sh)
    return out[:nq]


def cluster_hidden_states(activations_iter, k: int, kernel: KernelFn,
                          cfg: MBConfig, mesh: Mesh, init_batch=None,
                          **kw):
    """First-class integration with the LM substrate: cluster a stream of
    hidden-state batches (e.g. router inputs on MoE archs, HuBERT features).
    Initial centers = k-means++ on the first batch."""
    from repro.core.init import kmeans_plus_plus

    it = iter(activations_iter)
    first = init_batch if init_batch is not None else next(it)
    cidx = kmeans_plus_plus(jax.random.PRNGKey(cfg.k), jnp.asarray(first),
                            k, kernel)
    center_pts = jnp.asarray(first)[cidx]
    if init_batch is None:
        import itertools
        it = itertools.chain([first], it)
    return _fit_distributed_impl(it, center_pts, kernel, cfg, mesh, **kw)
