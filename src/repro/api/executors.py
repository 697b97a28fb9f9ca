"""Solver executors — declarative plan LOWERINGS onto the fit-loop core.

The loop skeleton itself — stage sequence, early stop, prefetch, the
precision/compress hooks, compiled-program caching, the resumable carry —
lives ONCE in :mod:`repro.core.loop`.  Each executor here supplies only
what genuinely differs between plan families, its :class:`LoopSpec`:

* the **sampler** (iid / weighted / nested / shard-local on-device),
* the **step body** (composed/fused ``make_step``, the cached warm+step
  pair, the shard_map ``_make_sampling_body``),
* the **mesh placement** (single device, data x model, restart x data x
  model) and
* the **donation signature** of its main fit program.

plus the orchestration that used to be copy-pasted across the ``fit_*``
family: PRNG key derivation (:func:`repro.api.keys.derive_fit_keys`),
init drawing (:func:`repro.core.init.draw_init`), divisibility
pad-and-mask (:func:`repro.core.distributed.pad_for_mesh`), and cache
lifecycle (build/warm/thread of the Gram tile cache).  A plan-vs-legacy
trajectory is therefore the *same* compiled computation, and new
cross-cutting axes register against the loop core once instead of once
per family (the PR-5/PR-7 lesson).

Executors are stateful on purpose: they cache the compiled programs
(jitted step / while_loop run) across ``fit`` calls — instance-local plus
the cross-executor registry in the loop core (``lookup_program``), which
is what makes ``KernelKMeans`` dispatch resolve at trace time with zero
per-step Python overhead (``tests/test_fused_step.py`` pins the reuse of
one executable across repeat fits).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api import keys as api_keys
from repro.api.config import SolverConfig
from repro.core import init as init_lib
from repro.core.loop import (  # noqa: F401  (canonical home: the loop core;
    # re-exported here for the historical import surface — estimator,
    # service snapshot/telemetry and tests all import these
    # names from repro.api.executors)
    FitCarry, FitOutcome, LoopSpec, _kernel_sig, _x_keyed_run, carry_of,
    clear_program_cache, lookup_program, outcome_from_carry, program_builds,
)
from repro.core.loop import loop_config as _loop_mb
from repro.core.loop import precision_plan, span
from repro.core.minibatch import (
    assign_chunked, center_distances_chunked, host_fit_loop, make_step,
    run_early_stopped, run_early_stopped_keyed, sampled_step_with_key,
)
from repro.core.state import init_state, window_size

_assign = jax.jit(assign_chunked, static_argnames=("chunk",))
_distances = jax.jit(center_distances_chunked, static_argnames=("chunk",))

# the unified root derivation (see repro.api.keys docstring); kept under
# the historical private name for in-repo callers
_derive_keys = api_keys.derive_fit_keys


class Executor:
    """Base class: holds (config, mesh), resolves the kernel once, and
    provides the serving-side defaults (predict / distances from the
    support-point view of the fitted state)."""

    name = "?"
    supports_partial_fit = False

    def __init__(self, config: SolverConfig, mesh=None):
        from repro.launch.mesh import auto_axes

        self.config = config
        self.mesh = None if mesh is None else auto_axes(mesh)
        self.kernel = config.make_kernel_fn()
        self.mb = config.mb_config()
        self._programs = {}      # instance-local compiled-program cache

    def _program(self, key, build, kernel_free: bool = False):
        """Compiled-program lookup through the loop core's cross-executor
        registry (:func:`repro.core.loop.lookup_program`): instance cache
        first, then the global registry keyed on the executor family +
        ``key`` + the kernel value signature.  ``key`` must capture the
        FULL closure signature minus the kernel — loop statics, mesh/axes,
        and the donated-argnum signature; ``kernel_free`` marks programs
        that take the kernel as a traced ARGUMENT (nothing kernel-shaped
        in the closure), which share unconditionally."""
        return lookup_program(self._programs, type(self).__name__, key,
                              build, kernel=self.kernel,
                              kernel_free=kernel_free)

    def _hooks(self, prefetch_ok: bool = True) -> tuple:
        """Which cross-cutting loop-core axes are ACTIVE under this plan —
        each axis has exactly one registration site in the loop core
        (prefetch: ``drive_fit_loop``; precision: ``precision_plan``;
        compress: ``compress_hook``)."""
        hooks = []
        if prefetch_ok and self.config.prefetch:
            hooks.append("prefetch")
        if precision_plan(self.kernel, self.mb).cdt is not None:
            hooks.append("precision:bf16")
        if self.mb.compress is not None and self.mb.compress.every > 0:
            hooks.append("compress")
        return tuple(hooks)

    def loop_spec(self) -> LoopSpec:
        """How this plan lowers onto the fit-loop core (the explain()
        surface).  Families override to describe their genuine deltas."""
        raise NotImplementedError

    # -- fitting ----------------------------------------------------------
    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            **kw) -> FitOutcome:
        raise NotImplementedError

    def resume(self, x, outcome: FitOutcome, iters: int) -> FitOutcome:
        raise NotImplementedError(
            f"plan {self.name!r} does not support partial_fit resumption")

    # -- serving ----------------------------------------------------------
    def serving_tuple(self, outcome: FitOutcome, x):
        """``(kernel, sup, coef, sqnorm)`` with ``sup`` the (k*W, d)
        support COORDINATES and ``kernel`` directly evaluable on
        coordinates — the uniform serving view every plan lowers to
        (index-data plans resolve their row ids here)."""
        state = outcome.state
        sup = x[state.idx.reshape(-1)]
        return self.kernel, sup, state.coef, state.sqnorm

    def predict(self, outcome: FitOutcome, x, xq, chunk: int = 4096):
        kern, sup, coef, sqnorm = self.serving_tuple(outcome, x)
        return _assign(kern, coef, sqnorm, sup, xq, chunk)

    def distances(self, outcome: FitOutcome, x, xq, chunk: int = 4096):
        kern, sup, coef, sqnorm = self.serving_tuple(outcome, x)
        return _distances(kern, coef, sqnorm, sup, xq, chunk)


def _sharded_batch_setup(executor: "Executor"):
    """Shared data-shard setup for every sharded-family executor: count
    the data shards and round the batch size UP to the next multiple
    (non-divisible batch sizes were a hard error on the legacy surface).
    Sets ``_shards``, ``effective_batch_size`` and ``_mb_eff``."""
    from repro.core.distributed import _data_shard_count

    executor._shards = _data_shard_count(executor.mesh,
                                         executor.config.data_axes)
    b = executor.mb.batch_size
    executor.effective_batch_size = -(-b // executor._shards) * \
        executor._shards
    executor._mb_eff = executor.mb._replace(
        batch_size=executor.effective_batch_size)


# ---------------------------------------------------------------- single
class SingleExecutor(Executor):
    """cache='none', distribution='single', restarts=1 — the paper's plain
    Algorithm-2 fit.  ``jit=True`` runs the whole early-stopped loop as one
    compiled ``lax.while_loop`` (legacy ``fit_jit``); ``jit=False`` (or a
    nested sampler / sample weights) drives it from the host (legacy
    ``fit``)."""

    name = "single"
    supports_partial_fit = True

    def _ensure_host_step(self):
        # donate the carried CenterState — the host loop threads it; a
        # kernel too large to key by value is a traced argument, as in
        # _jit_run
        mb, kernel = self.mb, self.kernel
        if _kernel_sig(kernel) is not None:
            return self._program(
                ("host_step", mb, ("donate", 0)),
                lambda: jax.jit(make_step(kernel, mb), donate_argnums=(0,)))
        prog = self._program(
            ("host_step", mb, ("donate", 1), True),
            lambda: jax.jit(lambda kern, state, x, bidx: make_step(
                kern, mb)(state, x, bidx), donate_argnums=(1,)),
            kernel_free=True)
        return functools.partial(prog, kernel)

    def _jit_run(self, kind: str, max_iters: int):
        kernel = self.kernel
        mb = _loop_mb(self.mb, self.config.early_stop, max_iters=max_iters)
        w = window_size(mb.batch_size, mb.tau)
        # donation: the resume program consumes the carried CenterState
        # and fit key (the FitCarry buffers) — steady-state partial_fit
        # chains allocate nothing new per call.  The init program donates
        # NOTHING: its key/init_idx can be caller-owned buffers (the
        # legacy shims pass the user's raw key), which callers may reuse.
        donate = () if kind == "init" else (1, 2)
        # a kernel too large to key by value (a Precomputed Gram) is the
        # program's first, traced argument: closed over, it would be
        # baked into the program as a constant
        as_arg = _kernel_sig(kernel) is None

        def build():
            def run(kern, x, start, key):
                step = make_step(kern, mb)
                state0 = (init_state(x, start, kern, w) if kind == "init"
                          else start)
                return run_early_stopped_keyed(
                    mb, sampled_step_with_key(step, x, mb), state0, key)

            if as_arg:
                return jax.jit(run, donate_argnums=tuple(
                    i + 1 for i in donate))
            return jax.jit(functools.partial(run, kernel),
                           donate_argnums=donate)

        prog = self._program((kind, mb, ("donate",) + donate, as_arg),
                             build, kernel_free=as_arg)
        return functools.partial(prog, kernel) if as_arg else prog

    def _use_jit(self, sample_weight):
        return (self.config.jit and sample_weight is None
                and self.config.sampler == "iid")

    def loop_spec(self) -> LoopSpec:
        jit = self._use_jit(None)
        return LoopSpec(
            lowering=self.name,
            driver="device" if jit else "host",
            sampler=self.config.sampler,
            step=f"make_step[{self.mb.step}]",
            placement="single device",
            donation=("state", "key") if jit else ("state",),
            hooks=self._hooks(prefetch_ok=not jit))

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            max_iters: Optional[int] = None, **kw) -> FitOutcome:
        cfg = self.config
        mb = self.mb if max_iters is None \
            else self.mb._replace(max_iters=max_iters)
        init_key, fit_key = _derive_keys(key, init_idx is not None,
                                         always_split)
        if init_idx is None:
            init_idx = init_lib.draw_init(init_key, x, mb.k, self.kernel,
                                          cfg.init)

        if self._use_jit(sample_weight):
            with span("kkm.run"):
                run = self._jit_run("init", mb.max_iters)
                state, iters, out_key = run(x, init_idx, fit_key)
            return FitOutcome(state=state, iters=iters, key=out_key,
                              steps=None)

        probs = None
        if sample_weight is not None:
            probs = jnp.asarray(sample_weight, jnp.float32)
            probs = probs / jnp.sum(probs)
        step = self._ensure_host_step()
        w = window_size(mb.batch_size, mb.tau)
        state0 = init_state(x, init_idx, self.kernel, w)
        state, history, out_key = host_fit_loop(
            lambda st, bidx: step(st, x, bidx), x.shape[0], mb, state0,
            fit_key, probs=probs, early_stop=cfg.early_stop,
            sampler=cfg.sampler, reuse=cfg.reuse, refresh=cfg.refresh,
            prefetch=cfg.prefetch)
        return FitOutcome(state=state, iters=len(history), history=history,
                          key=out_key, steps=len(history))

    def resume(self, x, outcome: FitOutcome, iters: int) -> FitOutcome:
        cfg = self.config
        if outcome.key is None:
            raise ValueError("outcome carries no fit key; cannot resume")
        prev = outcome.steps
        if prev is None:
            prev = int(outcome.iters)
        if self._use_jit(None):
            run = self._jit_run("resume", iters)
            state, it2, out_key = run(x, outcome.state, outcome.key)
            return FitOutcome(state=state, iters=it2, key=out_key,
                              steps=prev + int(it2))
        step = self._ensure_host_step()
        mb = self.mb._replace(max_iters=iters)
        state, history, out_key = host_fit_loop(
            lambda st, bidx: step(st, x, bidx), x.shape[0], mb,
            outcome.state, outcome.key, early_stop=cfg.early_stop,
            sampler=cfg.sampler, reuse=cfg.reuse, refresh=cfg.refresh,
            step0=prev, prefetch=cfg.prefetch)
        return FitOutcome(state=state, iters=len(history), history=history,
                          key=out_key, steps=prev + len(history))


# ---------------------------------------------------------- precomputed
class PrecomputedExecutor(Executor):
    """cache='precomputed', distribution='single', restarts=1 — pay the
    n^2 Gram ONCE (``repro.cache.PrecomputedGram``), then every iteration
    is pure gathers.  The right plan when n^2 fits on device (cache='auto'
    picks it below ``config.PRECOMPUTED_AUTO_MAX_ELEMS``).

    The compiled programs take the Gram kernel as a traced ARGUMENT (pk is
    a pytree), so refitting on new data of the same shape reuses the
    compiled loop instead of re-tracing — and can never bake stale Gram
    values in as constants."""

    name = "single_precomputed"

    def loop_spec(self) -> LoopSpec:
        jit = self.config.jit and self.config.sampler == "iid"
        return LoopSpec(
            lowering=self.name,
            driver="device" if jit else "host",
            sampler=self.config.sampler,
            step=f"make_step[{self.mb.step}] over a precomputed Gram "
                 "(traced argument; iterations are pure gathers)",
            placement="single device",
            donation=() if jit else ("state",),
            hooks=self._hooks(prefetch_ok=not jit))

    def _jit_run(self):
        mb = _loop_mb(self.mb, self.config.early_stop)
        w = window_size(mb.batch_size, mb.tau)

        def build():
            def run(pk, xi, init_idx, key):
                step = make_step(pk, mb)
                state0 = init_state(xi, init_idx, pk, w)
                return run_early_stopped_keyed(
                    mb, sampled_step_with_key(step, xi, mb), state0, key)

            return jax.jit(run)

        # the Gram kernel is a traced ARGUMENT, so the program's closure
        # is the loop config alone — shareable regardless of kernel size
        return self._program(("jit_run", mb), build, kernel_free=True)

    def _ensure_host_step(self):
        mb = self.mb

        def build():
            def hstep(pk, state, xi, bidx):
                return make_step(pk, mb)(state, xi, bidx)

            return jax.jit(hstep, donate_argnums=(1,))

        return self._program(("host_step", mb, ("donate", 1)), build,
                             kernel_free=True)

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            **kw) -> FitOutcome:
        from repro import cache as cache_lib

        cfg, mb = self.config, self.mb
        if sample_weight is not None:
            raise NotImplementedError("precomputed plan does not take "
                                      "sample weights (use cache='none')")
        pk, xi = cache_lib.as_kernel(cache_lib.precompute_gram(self.kernel,
                                                               x))
        init_key, fit_key = _derive_keys(key, init_idx is not None,
                                         always_split)
        if init_idx is None:
            init_idx = init_lib.draw_init(init_key, xi, mb.k, pk, cfg.init)
        if cfg.jit and cfg.sampler == "iid":
            state, iters, out_key = self._jit_run()(pk, xi, init_idx,
                                                    fit_key)
            return FitOutcome(state=state, iters=iters, key=out_key,
                              steps=None, x_view=xi)
        w = window_size(mb.batch_size, mb.tau)
        state0 = init_state(xi, init_idx, pk, w)
        step = self._ensure_host_step()
        state, history, out_key = host_fit_loop(
            lambda st, bidx: step(pk, st, xi, bidx), x.shape[0], mb,
            state0, fit_key, early_stop=cfg.early_stop,
            sampler=cfg.sampler, reuse=cfg.reuse, refresh=cfg.refresh,
            prefetch=cfg.prefetch)
        return FitOutcome(state=state, iters=len(history), history=history,
                          key=out_key, steps=len(history), x_view=xi)


# ------------------------------------------------------------------ lru
class CachedExecutor(Executor):
    """cache='lru', distribution='single', restarts=1 — the Gram tile
    cache fit (legacy ``fit_cached``): warm the batch+window row blocks,
    then the unchanged Algorithm-2 step serves every cross-kernel block
    from resident tiles.  Host-driven (the warm/step pair is one jitted
    program per iteration); the nested sampler keeps the working set
    resident."""

    name = "single_lru"

    def __init__(self, config, mesh=None):
        super().__init__(config, mesh)
        if self.mb.sqnorm_mode != "recompute" or self.mb.eval_mode != \
                "direct":
            # the incremental/delta variants evaluate cross-kernels inside
            # per-center vmaps, where cached lookups degrade to select
            # (both branches run) — correct but strictly slower
            raise ValueError("fit_cached supports the paper-faithful "
                             "sqnorm_mode='recompute' / eval_mode='direct' "
                             "(per-center vmapped kernel evals defeat the "
                             "cache's cond-skip)")

    def loop_spec(self) -> LoopSpec:
        return LoopSpec(
            lowering=self.name,
            driver="host",
            sampler=self.config.sampler,
            step="warm Gram tile cache + make_step (one jitted program)",
            placement="single device",
            donation=("state", "tile cache"),
            hooks=self._hooks())

    def _ensure_step(self):
        from repro import cache as cache_lib
        from repro.cache.tile_cache import warm

        kernel, mb = self.kernel, self.mb

        def build():
            def _cached_step(state, cache, xr, xi, batch_idx):
                # only (state, cache) are donated — the dataset and base
                # kernel buffers stay owned by the caller
                need = jnp.concatenate([batch_idx.astype(jnp.int32),
                                        state.idx.reshape(-1)])
                cache = warm(cache, kernel, xr, need)
                ck_t = cache_lib.CachedKernel(base=kernel, x=xr,
                                              cache=cache)
                st, info = make_step(ck_t, mb)(state, xi, batch_idx)
                return st, cache, info

            return jax.jit(_cached_step, donate_argnums=(0, 1))

        return self._program(
            ("cached_step", mb, self.config.cache_tile,
             self.config.cache_capacity, self.config.cache_dtype,
             ("donate", 0, 1)), build)

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            **kw) -> FitOutcome:
        from repro import cache as cache_lib

        cfg, mb = self.config, self.mb
        if sample_weight is not None:
            raise NotImplementedError("lru plan does not take sample "
                                      "weights (use cache='none')")
        init_key, fit_key = _derive_keys(key, init_idx is not None,
                                         always_split)
        if init_idx is None:
            init_idx = init_lib.draw_init(init_key, x, mb.k, self.kernel,
                                          cfg.init)
        # pad the CACHE's row space to a tile multiple (the tile store
        # wants tile | n); the sampler draws from the real n rows only, so
        # pad rows are never referenced — only their (wasted) tile slots
        # exist
        n = x.shape[0]
        pad = (-n) % cfg.cache_tile
        x_cache = x if pad == 0 else jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        ck, xi_full = cache_lib.make_cached(
            self.kernel, x_cache, tile=cfg.cache_tile,
            capacity=cfg.cache_capacity,
            dtype=jnp.dtype(cfg.cache_dtype))
        xi = xi_full[:n]
        w = window_size(mb.batch_size, mb.tau)
        state = init_state(xi, init_idx, ck, w)
        step = self._ensure_step()

        cache = ck.cache

        def step2(st, bidx):
            nonlocal cache
            st, cache, info = step(st, cache, x_cache, xi, bidx)
            return st, info

        state, history, out_key = host_fit_loop(
            step2, n, mb, state, fit_key,
            early_stop=cfg.early_stop, sampler=cfg.sampler,
            reuse=cfg.reuse, refresh=cfg.refresh, prefetch=cfg.prefetch)
        return FitOutcome(state=state, iters=len(history), history=history,
                          key=out_key, steps=len(history),
                          cache=ck._replace(cache=cache), x_view=xi)


# -------------------------------------------------------------- sharded
class ShardedExecutor(Executor):
    """distribution='sharded', cache='none', restarts=1 — the shard_map
    data x model path.  ``jit=True`` is the zero-host-sync while_loop with
    shard-local sampling (legacy ``fit_distributed_jit``); ``jit=False``
    drives the sharded step from a host batch stream (legacy
    ``fit_distributed``, batches drawn through the unified key stream via
    ``ClusterBatchPipeline(mode='keyed')``).

    Divisibility: non-divisible datasets are padded and the shard-local
    samplers masked (``pad_for_mesh`` + ``n_valid``); a batch size that
    does not divide the data shards is rounded UP to the next multiple
    (``effective_batch_size``) — both were hard errors on the legacy
    surface (``strict=True`` restores them for the shims)."""

    name = "sharded"

    def __init__(self, config, mesh=None):
        if mesh is None:
            from repro.launch.mesh import make_cluster_mesh
            mesh = make_cluster_mesh()
        super().__init__(config, mesh)
        _sharded_batch_setup(self)
        self._runs = {}

    def _mb_for(self, strict: bool):
        return self.mb if strict else self._mb_eff

    def _placement(self) -> str:
        cfg = self.config
        return (f"mesh {dict(self.mesh.shape)}: centers over "
                f"{cfg.model_axis!r}, batch over {tuple(cfg.data_axes)!r}")

    def loop_spec(self) -> LoopSpec:
        if self.config.jit:
            return LoopSpec(
                lowering=self.name, driver="device",
                sampler="shard-local on-device (stratified-uniform over "
                        "the data shards)",
                step="_make_sampling_body (shard_map)",
                placement=self._placement(), donation=("DistState",),
                hooks=self._hooks(prefetch_ok=False))
        return LoopSpec(
            lowering=self.name, driver="stream",
            sampler="host batch stream (keyed ClusterBatchPipeline)",
            step="make_dist_step (shard_map)",
            placement=self._placement(), donation=("DistState",),
            hooks=self._hooks())

    def _get_run(self, n_valid, strict: bool):
        mb = self._mb_for(strict)
        loop_mb = _loop_mb(mb, self.config.early_stop)
        cfg = self.config

        def build():
            from repro.core.distributed import make_dist_sampling_step

            step = make_dist_sampling_step(
                self.kernel, mb, self.mesh, cfg.data_axes,
                cfg.model_axis, n_valid=n_valid)

            def run(state, x, key):
                def step_with_key(st, kb):
                    st, info = step(st, x, kb)
                    return st, info.improvement

                return run_early_stopped(loop_mb, step_with_key, state,
                                         key)

            # donate the incoming DistState — it is freshly built and
            # device_put by fit() on every call, never caller-owned
            return jax.jit(run, donate_argnums=(0,))

        return self._program(
            ("dist_run", loop_mb, n_valid, strict, self.mesh,
             cfg.data_axes, cfg.model_axis, ("donate", 0)), build)

    def _resolve_centers(self, x, key, init_idx, center_pts, always_split):
        if center_pts is not None:
            _, fit_key = _derive_keys(key, True, always_split)
            return center_pts, fit_key
        init_key, fit_key = _derive_keys(key, init_idx is not None,
                                         always_split)
        if init_idx is None:
            init_idx = init_lib.draw_init(init_key, x, self.mb.k,
                                          self.kernel, self.config.init)
        return x[init_idx], fit_key

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            strict: bool = False, pad_fill: float = 0.0,
            **kw) -> FitOutcome:
        from repro.core.distributed import (
            init_dist_state, pad_for_mesh, shard_dataset, state_shardings)

        cfg = self.config
        mb = self._mb_for(strict)
        if sample_weight is not None:
            raise NotImplementedError("sharded plans do not take sample "
                                      "weights (use distribution='single')")
        center_pts, fit_key = self._resolve_centers(
            x, key, init_idx, center_pts, always_split)

        if not cfg.jit:
            return self._fit_host(x, center_pts, fit_key, mb)

        if strict:
            x_p, n_valid = x, None
        else:
            x_p, nv = pad_for_mesh(x, self.mesh, cfg.data_axes,
                                   fill=pad_fill)
            n_valid = None if x_p is x else nv
        w = window_size(mb.batch_size, mb.tau)
        state0 = jax.device_put(
            init_dist_state(center_pts, self.kernel, w),
            state_shardings(self.mesh, cfg.model_axis))
        xs = shard_dataset(x_p, self.mesh, cfg.data_axes)
        state, iters = self._get_run(n_valid, strict)(state0, xs, fit_key)
        return FitOutcome(state=state, iters=iters)

    def _fit_host(self, x, center_pts, fit_key, mb):
        import numpy as np

        from repro.data.pipeline import ClusterBatchPipeline

        pipe = ClusterBatchPipeline(np.asarray(x), batch=mb.batch_size,
                                    mode="keyed", key=fit_key)
        state, history = self.fit_stream(iter(pipe), center_pts, mb=mb)
        return FitOutcome(state=state, iters=len(history), history=history)

    def fit_stream(self, xb_stream, center_pts, mb=None):
        """Drive the sharded step from an arbitrary host iterator of
        (b, d) batches — the legacy ``fit_distributed`` surface (and
        ``cluster_hidden_states``).  With ``config.prefetch`` the next
        batch's host-to-device transfer overlaps the current sharded step
        (one-deep double buffering; bit-identical results)."""
        from repro.core.distributed import _fit_distributed_impl

        cfg = self.config
        return _fit_distributed_impl(
            xb_stream, center_pts, self.kernel, mb or self.mb, self.mesh,
            cfg.data_axes, cfg.model_axis, early_stop=cfg.early_stop,
            prefetch=cfg.prefetch)

    def serving_tuple(self, outcome: FitOutcome, x):
        state = outcome.state                     # DistState: coord windows
        k, w, d = state.pts.shape
        return (self.kernel, state.pts.reshape(k * w, d), state.coef,
                state.sqnorm)

    def predict(self, outcome: FitOutcome, x, xq, chunk: int = 4096):
        from repro.core.distributed import (
            dist_to_center_state, predict_distributed)

        kern, sup, coef, sqnorm = self.serving_tuple(outcome, x)
        return predict_distributed(dist_to_center_state(outcome.state),
                                   sup, xq, kern, self.mesh, chunk=chunk)


# ------------------------------------------------------ sharded + cache
class ShardedCachedExecutor(ShardedExecutor):
    """distribution='sharded', cache='lru', jit=True — per-data-shard Gram
    tile caches carried through the while_loop (legacy
    ``fit_distributed_cached_jit``)."""

    name = "sharded_lru"

    def loop_spec(self) -> LoopSpec:
        return super().loop_spec()._replace(
            step="cached _make_sampling_body (per-shard Gram tile caches "
                 "ride the while_loop carry)",
            donation=("DistState", "shard caches"))

    def _get_cached_run(self, x_real, n_valid, strict: bool):
        def build():
            from repro.core.distributed import (
                make_cached_dist_sampling_step)

            mb = self._mb_for(strict)
            loop_mb = _loop_mb(mb, self.config.early_stop)
            step = make_cached_dist_sampling_step(
                self.kernel, x_real, mb, self.mesh, self.config.data_axes,
                self.config.model_axis, n_valid=n_valid)

            def run(state, caches, x_idx, key):
                def step_with_key(carry, kb):
                    st, cc = carry
                    st, cc, info = step(st, cc, x_idx, kb)
                    return (st, cc), info.improvement

                (state, caches), iters = run_early_stopped(
                    loop_mb, step_with_key, (state, caches), key)
                return state, caches, iters

            # state + caches are the while_loop carry, freshly built per
            # fit — donate both so the loop reuses their buffers in place
            return jax.jit(run, donate_argnums=(0, 1))

        return _x_keyed_run(self._runs, ("cached", n_valid, strict),
                            x_real, build)

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            strict: bool = False, pad_fill: float = 0.0,
            **kw) -> FitOutcome:
        from repro.cache.cached_kernel import make_cached
        from repro.core.distributed import (
            init_dist_state, init_shard_caches, shard_dataset,
            state_shardings)

        cfg = self.config
        mb = self._mb_for(strict)
        if not cfg.jit:
            raise NotImplementedError(
                "the sharded lru plan is jit-only (the tile caches ride "
                "the while_loop carry); set jit=True or cache='none'")
        if sample_weight is not None:
            raise NotImplementedError("sharded plans do not take sample "
                                      "weights")
        init_key, fit_key = _derive_keys(key, init_idx is not None,
                                         always_split)
        if init_idx is None:
            init_idx = init_lib.draw_init(init_key, x, mb.k, self.kernel,
                                          cfg.init)
        cache_dtype = jnp.dtype(cfg.cache_dtype)
        # one padded row space serves BOTH constraints: divisible over the
        # data shards AND by the cache tile (pad_for_mesh's `multiple`).
        # Pad rows are masked out of the shard-local samplers (n_valid),
        # so only their tile slots exist — their coordinates never reach a
        # batch or a window.
        from repro.core.distributed import pad_for_mesh

        n = x.shape[0]
        if strict:
            x_cache, n_valid = x, None
        else:
            x_cache, nv = pad_for_mesh(x, self.mesh, cfg.data_axes,
                                       fill=pad_fill,
                                       multiple=cfg.cache_tile)
            n_valid = None if x_cache is x else nv
        ck0, xi_full = make_cached(self.kernel, x_cache,
                                   tile=cfg.cache_tile,
                                   capacity=cfg.cache_capacity,
                                   dtype=cache_dtype)
        xi = xi_full[:n]
        w = window_size(mb.batch_size, mb.tau)
        center_data = xi[init_idx]                  # (k, 1) index-data
        state0 = jax.device_put(
            init_dist_state(center_data, ck0, w),
            state_shardings(self.mesh, cfg.model_axis))
        xs = shard_dataset(xi_full, self.mesh, cfg.data_axes)
        caches0 = init_shard_caches(self.mesh, x_cache.shape[0],
                                    cfg.cache_tile, cfg.cache_capacity,
                                    cfg.data_axes, cache_dtype)
        run = self._get_cached_run(x_cache, n_valid, strict)
        state, caches, iters = run(state0, caches0, xs, fit_key)
        return FitOutcome(state=state, iters=iters, caches=caches,
                          x_view=xi)

    def serving_tuple(self, outcome: FitOutcome, x):
        state = outcome.state                  # DistState: index windows
        k, w, _ = state.pts.shape
        ids = state.pts[..., 0].reshape(-1).astype(jnp.int32)
        return self.kernel, x[ids], state.coef, state.sqnorm


# -------------------------------------------------------- multi-restart
class RestartExecutor(Executor):
    """restarts=R>1 — the best-of-R engine as one compiled program
    (legacy ``fit_restarts`` / ``MultiRestartEngine``), restart axis
    optionally device-sharded via a restart mesh.  The compiled R-restart
    program and the vmapped init draw are cached across fits."""

    name = "multi_restart"

    def __init__(self, config, mesh=None):
        super().__init__(config, mesh)
        self._run = None
        self._init_run = None

    def loop_spec(self) -> LoopSpec:
        cfg = self.config
        placement = ("single device (vmapped restart axis)"
                     if self.mesh is None else
                     f"restart axis sharded over mesh {dict(self.mesh.shape)}")
        return LoopSpec(
            lowering=self.name, driver="device",
            sampler=f"iid, R={cfg.restarts} independent per-restart key "
                    "streams",
            step=f"vmap(make_step[{self.mb.step}]) + shared-eval-batch "
                 "winner selection",
            placement=placement, donation=(),
            hooks=self._hooks(prefetch_ok=False))

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            _run=None, _init_run=None, **kw) -> FitOutcome:
        from repro.core.engine import (
            _fit_restarts, make_init_run, make_restart_run)

        cfg = self.config
        if sample_weight is not None:
            raise NotImplementedError("multi-restart plans do not take "
                                      "sample weights")
        if _run is None:
            if self._run is None:
                self._run = make_restart_run(self.kernel, self.mb,
                                             cfg.share_eval_gram)
                self._init_run = make_init_run(self.kernel, self.mb,
                                               cfg.init)
            _run, _init_run = self._run, self._init_run
        res = _fit_restarts(
            x, self.kernel, self.mb, key, cfg.restarts, init=cfg.init,
            init_idx=init_idx, mesh=self.mesh,
            restart_axis=cfg.restart_axis,
            eval_batch_size=cfg.eval_batch_size,
            share_eval_gram=cfg.share_eval_gram, _run=_run,
            _init_run=_init_run)
        return FitOutcome(state=res.state, iters=res.iters, engine=res)

    def predict(self, outcome: FitOutcome, x, xq, chunk: int = 4096):
        if self.mesh is None:
            return super().predict(outcome, x, xq, chunk=chunk)
        from repro.core.distributed import predict_distributed
        return predict_distributed(outcome.state, x, xq, self.kernel,
                                   self.mesh, chunk=chunk)


# ---------------------------------------------- fused restart x data x model
class FusedRestartExecutor(Executor):
    """restarts=R>1, distribution='sharded', jit — the ROADMAP's fused
    restart x data x model program, the first solver to land purely
    through the registry: R early-stopped SHARDED fits (each one the
    ``sharded`` plan's exact trajectory for its per-restart key) run as
    ONE compiled shard_map program on a ("restart", "data", "model") mesh
    (``launch.mesh.make_fused_mesh``), with shared-eval-batch winner
    selection running sharded and, for ``cache='lru'``, per-(restart,
    data-shard) Gram tile caches riding the while_loop carry
    (``init_shard_caches(..., restarts=R)``)."""

    name = "fused_restart_sharded"

    def __init__(self, config: SolverConfig, mesh=None):
        if mesh is None:
            from repro.launch.mesh import make_fused_mesh
            mesh = make_fused_mesh(config.restarts)
        super().__init__(config, mesh)
        self.restart_axis = config.restart_axis or "restart"
        if self.restart_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} carry no "
                f"{self.restart_axis!r} axis; build a fused mesh with "
                "repro.launch.mesh.make_fused_mesh(restarts)")
        _sharded_batch_setup(self)
        self._runs = {}
        self._init_run = None

    def loop_spec(self) -> LoopSpec:
        cfg = self.config
        cached = cfg.cache == "lru"
        return LoopSpec(
            lowering=self.name, driver="device",
            sampler="shard-local on-device, per-restart key streams",
            step=("cached _make_sampling_body x restarts (per-(restart, "
                  "shard) tile caches ride the carry)" if cached else
                  "_make_sampling_body x restarts (one shard_map program)"),
            placement=(f"mesh {dict(self.mesh.shape)}: restarts over "
                       f"{self.restart_axis!r}, centers over "
                       f"{cfg.model_axis!r}, batch over "
                       f"{tuple(cfg.data_axes)!r}"),
            donation=("shard caches",) if cached else (),
            hooks=self._hooks(prefetch_ok=False))

    def _eval_size(self, n: int) -> int:
        eb = self.config.eval_batch_size \
            or min(4 * self._mb_eff.batch_size, n)
        return -(-eb // self._shards) * self._shards

    def _keys_and_init(self, x, key, init_idx):
        cfg, restarts = self.config, self.config.restarts
        k_init, k_fit, k_eval = api_keys.restart_keys(key)
        if init_idx is None:
            if self._init_run is None:
                from repro.core.engine import make_init_run
                self._init_run = make_init_run(self.kernel, self._mb_eff,
                                               cfg.init)
            init_idx = self._init_run(api_keys.per_restart(k_init, restarts),
                                      x)
        if init_idx.shape[0] != restarts:
            raise ValueError(f"init_idx has {init_idx.shape[0]} rows, "
                             f"expected {restarts}")
        return init_idx, api_keys.per_restart(k_fit, restarts), k_eval

    def _get_run(self, n_valid, eval_size, x_real=None):
        def build():
            from repro.core.engine import make_fused_restart_run

            cfg = self.config
            return make_fused_restart_run(
                self.kernel, _loop_mb(self._mb_eff, cfg.early_stop),
                self.mesh, cfg.restarts, data_axes=cfg.data_axes,
                model_axis=cfg.model_axis, restart_axis=self.restart_axis,
                n_valid=n_valid, eval_size=eval_size, x_real=x_real)

        return _x_keyed_run(self._runs,
                            (n_valid, eval_size, x_real is not None),
                            x_real, build)

    def fit(self, x, key, init_idx=None, center_pts=None,
            sample_weight=None, always_split: bool = True,
            pad_fill: float = 0.0, **kw) -> FitOutcome:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.distributed import (
            fused_state_placements, init_dist_state, pad_for_mesh,
            restart_placements)
        from repro.core.minibatch import sample_batch

        cfg = self.config
        if not cfg.jit:
            raise NotImplementedError(
                "the fused restart plan is jit-only (R restarts x data x "
                "model in one compiled program); set jit=True, or "
                "distribution='single' for a host-driven restart loop")
        if sample_weight is not None:
            raise NotImplementedError("sharded plans do not take sample "
                                      "weights (use distribution='single')")
        if center_pts is not None:
            raise NotImplementedError("the fused restart plan draws R "
                                      "independent inits; pass init_idx "
                                      "of shape (R, k) instead of "
                                      "center_pts")
        init_idx, fit_keys, k_eval = self._keys_and_init(x, key, init_idx)
        n = x.shape[0]
        eval_size = self._eval_size(n)
        eval_idx = sample_batch(k_eval, n, eval_size)   # real rows only
        w = window_size(self._mb_eff.batch_size, self._mb_eff.tau)
        xspec = NamedSharding(self.mesh, P(tuple(cfg.data_axes), None))

        if cfg.cache == "lru":
            return self._fit_cached(x, init_idx, fit_keys, eval_idx,
                                    eval_size, w, xspec, pad_fill)

        x_p, nv = pad_for_mesh(x, self.mesh, cfg.data_axes, fill=pad_fill)
        n_valid = None if x_p is x else nv
        state0 = jax.device_put(
            jax.vmap(lambda cp: init_dist_state(cp, self.kernel, w))(
                x[init_idx]),
            fused_state_placements(self.mesh, self.restart_axis,
                                   cfg.model_axis))
        (fit_keys,), _ = restart_placements(self.mesh, self.restart_axis,
                                            (fit_keys,))
        run = self._get_run(n_valid, eval_size)
        res = run(state0, jax.device_put(x_p, xspec),
                  jax.device_put(x[eval_idx], xspec), fit_keys)
        return FitOutcome(state=res.state, iters=res.iters, engine=res)

    def _fit_cached(self, x, init_idx, fit_keys, eval_idx, eval_size, w,
                    xspec, pad_fill):
        from repro.cache.cached_kernel import make_cached
        from repro.core.distributed import (
            fused_state_placements, init_dist_state, init_shard_caches,
            pad_for_mesh, restart_placements)

        cfg = self.config
        cache_dtype = jnp.dtype(cfg.cache_dtype)
        n = x.shape[0]
        x_cache, nv = pad_for_mesh(x, self.mesh, cfg.data_axes,
                                   fill=pad_fill, multiple=cfg.cache_tile)
        n_valid = None if x_cache is x else nv
        ck0, xi_full = make_cached(self.kernel, x_cache,
                                   tile=cfg.cache_tile,
                                   capacity=cfg.cache_capacity,
                                   dtype=cache_dtype)
        xi = xi_full[:n]
        state0 = jax.device_put(
            jax.vmap(lambda cp: init_dist_state(cp, ck0, w))(xi[init_idx]),
            fused_state_placements(self.mesh, self.restart_axis,
                                   cfg.model_axis))
        (fit_keys,), _ = restart_placements(self.mesh, self.restart_axis,
                                            (fit_keys,))
        caches0 = init_shard_caches(
            self.mesh, x_cache.shape[0], cfg.cache_tile, cfg.cache_capacity,
            cfg.data_axes, cache_dtype, restarts=cfg.restarts,
            restart_axis=self.restart_axis)
        run = self._get_run(n_valid, eval_size, x_real=x_cache)
        res, caches = run(state0, caches0, jax.device_put(xi_full, xspec),
                          jax.device_put(x[eval_idx], xspec), fit_keys)
        return FitOutcome(state=res.state, iters=res.iters, engine=res,
                          caches=caches, x_view=xi)

    def serving_tuple(self, outcome: FitOutcome, x):
        state = outcome.state                 # DistState, model-sharded
        k, w, d = state.pts.shape
        if self.config.cache == "lru":        # index windows
            ids = state.pts[..., 0].reshape(-1).astype(jnp.int32)
            return self.kernel, x[ids], state.coef, state.sqnorm
        return (self.kernel, state.pts.reshape(k * w, d), state.coef,
                state.sqnorm)

    def predict(self, outcome: FitOutcome, x, xq, chunk: int = 4096):
        from repro.core.distributed import (
            dist_to_center_state, predict_distributed)

        kern, sup, coef, sqnorm = self.serving_tuple(outcome, x)
        return predict_distributed(dist_to_center_state(outcome.state),
                                   sup, xq, kern, self.mesh, chunk=chunk)
