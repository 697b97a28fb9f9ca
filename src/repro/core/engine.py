"""Multi-restart clustering engine.

Mini-batch (kernel) k-means is a stochastic descent: Tang & Monteleoni's
analysis (and sklearn practice) motivates running R independent restarts
and keeping the best.  Naively that multiplies wall-clock by R; here the
R restarts become ONE compiled program:

* ``fit_restarts`` vmaps the fully-on-device ``fit_jit`` loop (init ->
  while_loop -> early stop) over R PRNG keys and R init index sets.  The
  vmapped ``lax.while_loop`` keeps stepping until every restart has
  terminated (finished lanes are masked), so early-stopping still works
  per-restart.
* Every restart's final centers are scored on one SHARED eval batch
  (``batch_objective``) and the argmin state is selected on-device — the
  host only ever sees the winner.
* With a ``mesh`` the restart axis is sharded across devices: R restarts
  x D devices run in a single compiled program, XLA partitioning the
  batched kernel evaluations over the 'restart' axis.  On top of a
  multi-axis mesh the same engine serves sharded prediction via
  ``repro.core.distributed.predict_distributed``.

``MultiRestartEngine`` is the stateful convenience wrapper (caches the
compiled program across fits of same-shaped data).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.api import keys as api_keys
from repro.core import init as init_lib
from repro.core.kernel_fns import KernelFn, diag_of
from repro.core.loop import run_early_stopped, run_early_stopped_keyed
from repro.core.minibatch import (
    MBConfig, batch_objective, batch_objective_from_rows,
    make_step, sample_batch, sampled_step_with_key,
)
from repro.core.state import CenterState, init_state, window_size

# Auto-enable shared eval-Gram scoring while the (eb, n) row strip stays
# under ~64 MB f32 — beyond that, per-restart recomputation is cheaper than
# the memory.
_SHARED_EVAL_GRAM_MAX_ELEMS = 16 * 2 ** 20


class EngineResult(NamedTuple):
    state: CenterState       # best restart's centers
    objective: jax.Array     # ()  best shared-eval-batch objective
    objectives: jax.Array    # (R,) per-restart eval objectives
    iters: jax.Array         # (R,) iterations each restart ran
    best: jax.Array          # ()  int32 winning restart index


def _restart_axis_of(mesh: Mesh, restart_axis: Optional[str]) -> str:
    if restart_axis is not None:
        return restart_axis
    return mesh.axis_names[0]


def make_init_run(kernel: KernelFn, cfg: MBConfig, init: str = "kmeans++"):
    """Jitted, vmapped per-restart init draw: (ikeys (R, 2), x) -> (R, k)
    center indices.  Cache alongside make_restart_run's program (as
    MultiRestartEngine does) so repeated fits pay no re-trace."""
    if init == "kmeans++":
        def one(kk, x):
            return init_lib.kmeans_plus_plus(kk, x, cfg.k, kernel)
    elif init == "random":
        def one(kk, x):
            return init_lib.random_init(kk, x.shape[0], cfg.k)
    else:
        raise ValueError(init)
    return jax.jit(jax.vmap(one, in_axes=(0, None)))


def _fit_restarts(x: jax.Array, kernel: KernelFn, cfg: MBConfig,
                  key: jax.Array, restarts: int,
                  init: str = "kmeans++",
                  init_idx: Optional[jax.Array] = None,
                  mesh: Optional[Mesh] = None,
                  restart_axis: Optional[str] = None,
                  eval_batch_size: Optional[int] = None,
                  share_eval_gram: Optional[bool] = None,
                  _run=None, _init_run=None) -> EngineResult:
    """Implementation behind :func:`fit_restarts` and the ``multi_restart``
    solver plan (repro.api.executors)."""
    n = x.shape[0]
    k_init, k_fit, k_eval = api_keys.restart_keys(key)
    if init_idx is None:
        ikeys = api_keys.per_restart(k_init, restarts)
        draw = _init_run if _init_run is not None \
            else make_init_run(kernel, cfg, init)
        init_idx = draw(ikeys, x)
    if init_idx.shape[0] != restarts:
        raise ValueError(f"init_idx has {init_idx.shape[0]} rows, "
                         f"expected {restarts}")
    fit_keys = api_keys.per_restart(k_fit, restarts)
    eb = eval_batch_size or min(4 * cfg.batch_size, n)
    eval_idx = sample_batch(k_eval, n, eb)

    if mesh is not None:
        from repro.core.distributed import restart_placements
        ax = _restart_axis_of(mesh, restart_axis)
        if restarts % mesh.shape[ax]:
            raise ValueError(
                f"restarts={restarts} not divisible by mesh axis "
                f"'{ax}' of size {mesh.shape[ax]}")
        (fit_keys, init_idx), (x, eval_idx) = restart_placements(
            mesh, ax, (fit_keys, init_idx), (x, eval_idx))

    run = _run if _run is not None \
        else make_restart_run(kernel, cfg, share_eval_gram)
    return run(x, fit_keys, init_idx, eval_idx)


def fit_restarts(x: jax.Array, kernel: KernelFn, cfg: MBConfig,
                 key: jax.Array, restarts: int,
                 init: str = "kmeans++",
                 init_idx: Optional[jax.Array] = None,
                 mesh: Optional[Mesh] = None,
                 restart_axis: Optional[str] = None,
                 eval_batch_size: Optional[int] = None,
                 share_eval_gram: Optional[bool] = None,
                 _run=None, _init_run=None) -> EngineResult:
    """Run R independent mini-batch kernel k-means fits in one compiled
    program and return the best (plus per-restart diagnostics).

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(restarts=R)`` — this shim resolves exactly that plan
        and delegates to it (the estimator additionally caches the compiled
        R-restart program across fits, like ``MultiRestartEngine`` does).

    ``init_idx``: optional (R, k) precomputed initial center indices —
    otherwise R independent k-means++ (or random) draws are made, vmapped
    on-device.  With ``mesh``, R must be divisible by the restart-axis size
    (see ``launch.mesh.make_restart_mesh``).
    """
    from repro.api import legacy as _legacy
    _legacy.warn_legacy("repro.core.fit_restarts",
                        "KernelKMeans(SolverConfig(restarts=R))")
    return _legacy.fit_restarts(
        x, kernel, cfg, key, restarts, init=init, init_idx=init_idx,
        mesh=mesh, restart_axis=restart_axis,
        eval_batch_size=eval_batch_size, share_eval_gram=share_eval_gram,
        _run=_run, _init_run=_init_run)


def make_restart_run(kernel: KernelFn, cfg: MBConfig,
                     share_eval_gram: Optional[bool] = None):
    """Build the jitted R-restart program: (x, fit_keys(R,2), init_idx(R,k),
    eval_idx(eb,)) -> EngineResult.  Kernel params are closed over (they are
    array pytrees, so they cannot be static jit args); callers that fit
    repeatedly should cache the returned function — MultiRestartEngine does.

    ``share_eval_gram``: score every restart from ONE precomputed
    K(x_eval, x) row strip (a Gram-tile-cache-style reuse: the strip is
    computed once and each restart's support cross block is a column
    gather) instead of R independent cross-kernel evaluations.  Default
    ``None`` auto-enables while the strip stays small (eb * n <=
    ``_SHARED_EVAL_GRAM_MAX_ELEMS``)."""
    w = window_size(cfg.batch_size, cfg.tau)
    step = make_step(kernel, cfg)

    def fit_one(x, key, idx0):
        state0 = init_state(x, idx0, kernel, w)
        return run_early_stopped(cfg, sampled_step_with_key(step, x, cfg),
                                 state0, key)

    @jax.jit
    def run(x, fit_keys, init_idx, eval_idx):
        states, iters = jax.vmap(
            lambda kk, ii: fit_one(x, kk, ii))(fit_keys, init_idx)
        share = share_eval_gram
        if share is None:
            share = (x.shape[0] * eval_idx.shape[0]
                     <= _SHARED_EVAL_GRAM_MAX_ELEMS)
        if share:
            from repro.core.kernel_fns import kernel_cross
            xe = x[eval_idx]
            gram_rows = kernel_cross(kernel, xe, x)        # (eb, n), once
            diag_e = diag_of(kernel, xe)
            objs = jax.vmap(
                lambda s: batch_objective_from_rows(gram_rows, diag_e,
                                                    s))(states)
        else:
            objs = jax.vmap(
                lambda s: batch_objective(kernel, s, x, eval_idx))(states)
        best = jnp.argmin(objs).astype(jnp.int32)
        best_state = jax.tree.map(lambda a: a[best], states)
        return EngineResult(state=best_state, objective=objs[best],
                            objectives=objs, iters=iters, best=best)

    return run


def make_fused_restart_run(kernel: KernelFn, cfg: MBConfig, mesh: Mesh,
                           restarts: int,
                           data_axes=("data",), model_axis: str = "model",
                           restart_axis: str = "restart",
                           n_valid: Optional[int] = None,
                           eval_size: int = 512,
                           x_real: Optional[jax.Array] = None):
    """Build the jitted fused restart x data x model program — the
    ROADMAP's "one compiled program" for R restarts of the SHARDED step,
    landed behind the ``fused_restart_sharded`` solver registration.

    Composition: the mesh carries a ``restart_axis`` alongside the
    data/model axes; each restart group runs the unchanged shard-local
    sampled Algorithm-2 body (``distributed._make_sampling_body``) in its
    own early-stopped ``lax.while_loop`` — devices of one group share
    bit-identical improvements, so their loop trip counts (and collectives)
    agree, while different groups stop independently with no cross-restart
    sync inside the loop.  Restarts beyond the restart-axis size run as
    sequential lanes on their group (``R_loc = R / r_size``), which is
    exactly R sequential sharded fits per group — trajectories are
    BIT-EXACT against running each restart through
    :func:`distributed.make_dist_sampling_step` with the same key.

    Winner selection runs sharded on one shared eval batch: per-lane
    objectives are psum'd over the data axes, all_gather'd over
    ``restart_axis``, and the argmin state is broadcast back with a masked
    psum — the host only ever sees the winner.

    ``cfg`` must already be the LOOP config (epsilon lowered for
    ``early_stop=False`` — see ``repro.core.loop.loop_config``).  ``eval_size`` is
    the global eval-batch row count (must divide the data shards).

    Uncached (``x_real=None``): returns
    ``run(state0, x, xe, fit_keys) -> EngineResult`` where ``state0`` is
    the restart-stacked coordinate-window DistState, ``x`` the (padded)
    dataset sharded over ``data_axes``, ``xe`` the (eval_size, d) eval
    rows sharded likewise, ``fit_keys`` (R, 2) sharded over
    ``restart_axis``.  Cached (``x_real`` = real coordinates): ``x`` is
    the (n, 1) index-data view, ``state0`` index windows, and the
    signature becomes ``run(state0, caches, x_idx, xe, fit_keys) ->
    (EngineResult, caches)`` with per-(restart, data-shard) tile caches
    from ``init_shard_caches(..., restarts=R)`` (``xe`` stays REAL
    coordinates — scoring resolves window ids through ``x_real``)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed as D
    from repro.core.distributed import DistState
    from repro.core.kernel_fns import kernel_cross, kernel_diag
    data_axes = tuple(data_axes)
    r_size = mesh.shape[restart_axis]
    if restarts % r_size:
        raise ValueError(f"restarts={restarts} not divisible by mesh axis "
                         f"{restart_axis!r} of size {r_size}")
    r_loc = restarts // r_size
    cached = x_real is not None
    body = (D._make_cached_sampling_body(kernel, x_real, cfg, mesh,
                                         data_axes, model_axis, n_valid)
            if cached else
            D._make_sampling_body(kernel, cfg, mesh, data_axes, model_axis,
                                  n_valid))

    def eval_objective(st, xe_loc):
        """Shared-eval-batch objective of one lane's final centers,
        sharded over data (rows) x model (centers)."""
        k_loc, w, d = st.pts.shape
        if cached:
            # index windows: resolve support ids through the real
            # coordinates (``kernel`` is the BASE kernel in cached mode)
            ids = st.pts[..., 0].reshape(-1).astype(jnp.int32)
            sup = x_real[ids]
        else:
            sup = st.pts.reshape(k_loc * w, d)
        cross = kernel_cross(kernel, xe_loc, sup).astype(jnp.float32)
        p = jnp.einsum("bkw,kw->bk",
                       cross.reshape(xe_loc.shape[0], k_loc, w), st.coef)
        diag_e = kernel_diag(kernel, xe_loc).astype(jnp.float32)
        d_loc = diag_e[:, None] - 2.0 * p + st.sqnorm[None, :]
        d_all = jax.lax.all_gather(d_loc, model_axis, axis=1, tiled=True)
        part = jnp.sum(jnp.min(d_all, axis=1))
        for ax in data_axes:
            part = jax.lax.psum(part, ax)
        return part / eval_size

    def select_winner(states, objs_loc, iters_loc):
        """all_gather diagnostics over the restart axis and broadcast the
        argmin lane's (model-sharded) state to every restart group."""
        objs = jax.lax.all_gather(objs_loc, restart_axis, axis=0,
                                  tiled=True)                      # (R,)
        iters = jax.lax.all_gather(iters_loc, restart_axis, axis=0,
                                   tiled=True)                     # (R,)
        best = jnp.argmin(objs).astype(jnp.int32)
        g = jax.lax.axis_index(restart_axis)
        in_group = (best // r_loc) == g
        pick = jnp.where(in_group, best % r_loc, 0)
        win = jax.tree.map(
            lambda a: jax.lax.psum(
                jnp.where(in_group, a[pick], jnp.zeros_like(a[pick])),
                restart_axis),
            states)
        return win, objs, iters, best

    st_stacked = DistState(
        pts=P(restart_axis, model_axis, None, None),
        coef=P(restart_axis, model_axis, None),
        head=P(restart_axis, model_axis),
        sqnorm=P(restart_axis, model_axis),
        counts=P(restart_axis, model_axis),
        step=P(restart_axis))
    st_win = D._state_specs(model_axis)

    def run_lanes(state_st, caches_st, x_loc, xe_loc, keys_loc):
        """The shared per-group driver: each local restart lane runs its
        own early-stopped sharded fit (threading its tile cache through
        the carry when ``caches_st`` is given), then the winner is picked
        across the whole restart axis."""
        states, caches, iters, objs = [], [], [], []
        for lane in range(r_loc):
            st_l = jax.tree.map(lambda a: a[lane], state_st)
            if caches_st is None:
                def swk(st, kb):
                    st, info = body(st, x_loc, kb)
                    return st, info.improvement

                st_f, it_l, _ = run_early_stopped_keyed(
                    cfg, swk, st_l, keys_loc[lane])
            else:
                cc_l = jax.tree.map(lambda a: a[lane], caches_st)

                def swk(carry, kb):
                    st, cc = carry
                    st, cc, info = body(st, cc, x_loc, kb)
                    return (st, cc), info.improvement

                (st_f, cc_l), it_l, _ = run_early_stopped_keyed(
                    cfg, swk, (st_l, cc_l), keys_loc[lane])
                caches.append(cc_l)
            states.append(st_f)
            iters.append(it_l)
            objs.append(eval_objective(st_f, xe_loc))
        states = jax.tree.map(lambda *a: jnp.stack(a), *states)
        win, objs, iters, best = select_winner(states, jnp.stack(objs),
                                               jnp.stack(iters))
        caches_out = (jax.tree.map(lambda *a: jnp.stack(a), *caches)
                      if caches_st is not None else None)
        return win, caches_out, objs, iters, best

    if not cached:
        def fused_local(state_st, x_loc, xe_loc, keys_loc):
            win, _, objs, iters, best = run_lanes(state_st, None, x_loc,
                                                  xe_loc, keys_loc)
            return win, objs, iters, best

        fn = shard_map(
            fused_local, mesh=mesh,
            in_specs=(st_stacked, P(data_axes, None), P(data_axes, None),
                      P(restart_axis, None)),
            out_specs=(st_win, P(), P(), P()),
            check_vma=False)

        # NOTE: state0 is deliberately NOT donated — only the winning
        # lane's (k, ...) state leaves the program, so the stacked
        # (R, k, ...) input can never alias an output and XLA would
        # reject the donation (the while_loop reuses the carry buffers
        # internally regardless)
        @jax.jit
        def run(state0, x, xe, fit_keys):
            win, objs, iters, best = fn(state0, x, xe, fit_keys)
            return EngineResult(state=win, objective=objs[best],
                                objectives=objs, iters=iters, best=best)

        return run

    from repro.cache.tile_cache import GramTileCache

    def fused_local_cached(state_st, caches_st, x_loc, xe_loc, keys_loc):
        return run_lanes(state_st, caches_st, x_loc, xe_loc, keys_loc)

    cache_specs = GramTileCache(
        store=P(restart_axis, data_axes, None, None, None),
        keys=P(restart_axis, data_axes, None),
        stamp=P(restart_axis, data_axes, None),
        clock=P(restart_axis, data_axes),
        hits=P(restart_axis, data_axes),
        misses=P(restart_axis, data_axes),
        evictions=P(restart_axis, data_axes))

    fn = shard_map(
        fused_local_cached, mesh=mesh,
        in_specs=(st_stacked, cache_specs, P(data_axes, None),
                  P(data_axes, None), P(restart_axis, None)),
        out_specs=(st_win, cache_specs, P(), P(), P()),
        check_vma=False)

    # the per-(restart, shard) tile caches round-trip the program with
    # identical shapes — donate them so the whole cache store updates in
    # place (state0 is not donatable: only the winner's (k, ...) slice
    # leaves, see the uncached variant above)
    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(state0, caches0, x_idx, xe, fit_keys):
        win, caches, objs, iters, best = fn(state0, caches0, x_idx, xe,
                                            fit_keys)
        return EngineResult(state=win, objective=objs[best],
                            objectives=objs, iters=iters,
                            best=best), caches

    return run


class MultiRestartEngine:
    """Stateful wrapper: holds (kernel, cfg, restarts, mesh) and exposes
    ``fit`` / ``predict``.  ``mesh=None`` runs all restarts on one device
    (still one compiled program — the vmap batches every kernel matmul);
    with a mesh the restart axis is device-sharded and ``predict`` shards
    query rows for serving."""

    def __init__(self, kernel: KernelFn, cfg: MBConfig, restarts: int = 4,
                 mesh: Optional[Mesh] = None,
                 restart_axis: Optional[str] = None,
                 init: str = "kmeans++",
                 eval_batch_size: Optional[int] = None,
                 share_eval_gram: Optional[bool] = None):
        if restarts < 1:
            raise ValueError("restarts must be >= 1")
        self.kernel = kernel
        self.cfg = cfg
        self.restarts = restarts
        self.mesh = mesh
        self.restart_axis = restart_axis
        self.init = init
        self.eval_batch_size = eval_batch_size
        self.share_eval_gram = share_eval_gram
        self.result: Optional[EngineResult] = None
        self._x: Optional[jax.Array] = None
        self._run = None       # compiled fit program cache
        self._init_run = None  # compiled init-draw cache

    def fit(self, x: jax.Array, key: jax.Array) -> EngineResult:
        """.. deprecated::
            Use :class:`repro.api.KernelKMeans` with
            ``SolverConfig(restarts=R)`` — it caches the compiled program
            the same way and serves ``predict`` through the same paths."""
        from repro.api import legacy as _legacy
        _legacy.warn_legacy("repro.core.engine.MultiRestartEngine.fit",
                            "KernelKMeans(SolverConfig(restarts=R))")
        if self._run is None:
            self._run = make_restart_run(self.kernel, self.cfg,
                                         self.share_eval_gram)
            self._init_run = make_init_run(self.kernel, self.cfg, self.init)
        self.result = _fit_restarts(
            x, self.kernel, self.cfg, key, self.restarts, init=self.init,
            mesh=self.mesh, restart_axis=self.restart_axis,
            eval_batch_size=self.eval_batch_size, _run=self._run,
            _init_run=self._init_run)
        self._x = x
        return self.result

    def predict(self, xq: jax.Array, chunk: int = 4096) -> jax.Array:
        """Assign query points to the best restart's centers.  With a mesh
        the queries are row-sharded over every non-'model' axis (the
        serving path for large query sets)."""
        if self.result is None:
            raise RuntimeError("fit() first")
        from repro.core.minibatch import predict
        if self.mesh is None:
            return predict(self.result.state, self._x, xq, self.kernel,
                           chunk=chunk)
        from repro.core.distributed import predict_distributed
        return predict_distributed(self.result.state, self._x, xq,
                                   self.kernel, self.mesh, chunk=chunk)
