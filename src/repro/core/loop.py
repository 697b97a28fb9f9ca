"""The ONE fit-loop core behind every solver plan.

The paper's algorithm is a single loop — sample a batch, assign against
the current truncated centers, update, check the early-stop condition —
but the repo used to re-implement that loop once per executor family, and
every cross-cutting axis (precision, compress, prefetch, donation,
program caching) had to be threaded through all of them by hand.  This
module owns the loop skeleton exactly once:

* **Drivers** — the two ways the canonical stage sequence executes:

  - :func:`drive_fit_loop`: the host-driven early-stopped loop (python
    ``for`` + per-step improvement sync).  Generic over where batches
    come from: the single-device plans draw from the unified key stream,
    the sharded stream plan pulls from a host iterator — both are thin
    adapters (``minibatch.host_fit_loop``,
    ``distributed._fit_distributed_impl``).  One-deep **prefetch** is
    implemented HERE and nowhere else.
  - :func:`run_early_stopped_keyed` / :func:`run_early_stopped`: the
    on-device driver — the whole early-stopped loop as one
    ``lax.while_loop`` (jit / shard_map / vmap'd restart plans all close
    over it).

* **Cross-cutting axis hooks**, each registered once:

  - :func:`precision_plan` — the ``compute_dtype`` axis (bf16 kernel
    evals, f32 accumulation; index-data kernels exempt).
  - :func:`compress_hook` — the landmark-compression cadence hook, for
    both the single-device step and the shard-local step.
  - :func:`lookup_program` — donation-aware compiled-program caching
    (the ``program_builds()`` counter lives here).

* **Carry/telemetry** — :class:`FitOutcome` (what a fit produced),
  :class:`FitCarry` (the resumable part ``partial_fit`` / ``save`` need),
  and the fit path's trace names (:data:`STAGES`, :func:`span`,
  :func:`scope`).

* **Lowering description** — :class:`LoopSpec` + :func:`stages`: every
  executor family describes itself as a declarative lowering (sampler,
  step body, placement, donation, active hooks) over this core;
  ``KernelKMeans.explain()`` renders it.

Adding a new axis to the fit loop means touching the one relevant hook
here plus the lowerings that opt in — not seven executor families
(ROADMAP: multi-host mesh, tile autotuner, embedding-stream producer).
The refactor contract is bit-identity: every emitted program is the
historical one (tests/test_api_grid.py pins the full plan grid).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import keys as api_keys

# ---------------------------------------------------------------------------
# Carry / telemetry — the loop's outputs, shared by every lowering.


@dataclasses.dataclass
class FitOutcome:
    """What a plan's ``fit`` produced.  ``state`` is a ``CenterState``
    (single-device plans) or ``DistState`` (sharded plans); the optional
    fields carry plan-specific artifacts (tile cache, engine diagnostics,
    the carried PRNG key for ``partial_fit`` resumption)."""

    state: Any
    iters: Any                              # python int or on-device scalar
    history: Optional[List[dict]] = None    # host-driven plans only
    key: Optional[jax.Array] = None         # carried fit-stream key
    steps: int = 0                          # completed host-loop steps
    cache: Any = None                       # CachedKernel (single lru plan)
    caches: Any = None                      # stacked per-shard tile caches
    engine: Any = None                      # EngineResult (multi-restart)
    x_view: Any = None                      # index-data view (lru/precomp)


class FitCarry(NamedTuple):
    """The resumable part of a fit — everything ``partial_fit`` needs to
    continue the batch stream bit-exactly, and therefore everything
    ``KernelKMeans.save`` must round-trip: the full center state, the
    carried PRNG fit key, the completed-step cursor (the nested sampler's
    schedule position), and the iteration count."""

    state: Any                    # CenterState (single-device plans)
    key: jax.Array                # carried fit-stream key
    steps: Optional[int]          # host-loop cursor; None on jit-only fits
    iters: int


def carry_of(outcome: FitOutcome) -> Optional[FitCarry]:
    """The serializable resume carry of an outcome, or None when the plan
    that produced it cannot resume (no carried key)."""
    if outcome is None or outcome.key is None:
        return None
    return FitCarry(state=outcome.state, key=outcome.key,
                    steps=outcome.steps, iters=int(outcome.iters))


def outcome_from_carry(carry: FitCarry) -> FitOutcome:
    """Rehydrate a deserialized carry into a resumable outcome."""
    return FitOutcome(state=carry.state, iters=carry.iters, key=carry.key,
                      steps=carry.steps)


# ---------------------------------------------------------------------------
# The carry-guard axis (non-finite repair + dead-center reseed), registered
# once — like the compress/precision hooks, the clean path is the identity.


class CarryGuardReport(NamedTuple):
    """What :func:`guard_carry` did to one carry.  ``patched`` counts
    non-finite float entries zeroed across the state leaves; ``reseeded``
    counts dead centers re-initialized from the dataset.  Both zero means
    the carry was returned UNTOUCHED (same object — bit-identity by
    construction)."""

    patched: int
    reseeded: int

    @property
    def clean(self) -> bool:
        return self.patched == 0 and self.reseeded == 0


def guard_carry(carry: Optional[FitCarry], *, x=None, kernel=None,
                seed: int = 0, faults=None):
    """THE carry-guard registration site: repair a host
    :class:`FitCarry` whose center state went degenerate — non-finite
    coefficients/norms/counts (a poisoned batch, a bad reduction, a
    hardware fault) are zeroed, and DEAD centers (no finite nonzero
    coefficient left — the empty-cluster instability Tang & Monteleoni
    analyze for stochastic k-means) are reseeded as single data points
    drawn deterministically from ``(seed, fit step, center)``.

    A CLEAN carry is returned as the SAME object with a zero report —
    callers on the clean path stay bit-identical to not calling the
    guard at all (the ``compress="off"`` / ``cdt=None`` identity
    convention).  Reseeding needs ``x`` (host dataset the carry's
    indices refer to; non-finite rows are never picked) and ``kernel``
    (for the reseeded center's ``sqnorm``); without them dead centers
    are left zeroed but still counted.

    ``faults``: an optional :class:`repro.service.faults.FaultPlan`
    whose ``loop.carry`` site fires here — a ``nan`` event poisons the
    carry deterministically BEFORE the check, so the chaos harness
    exercises exactly this repair path."""
    if carry is None:
        return carry, CarryGuardReport(0, 0)
    if faults is not None:
        ev = faults.fire("loop.carry")
        if ev is not None and ev.kind == "nan" and \
                hasattr(carry.state, "coef"):
            carry = carry._replace(state=carry.state._replace(
                coef=faults.nan_leaf(np.asarray(carry.state.coef), ev)))
    state = carry.state
    if not hasattr(state, "coef"):          # only CenterState-shaped
        return carry, CarryGuardReport(0, 0)
    coef = np.asarray(state.coef)
    sqnorm = np.asarray(state.sqnorm)
    counts = np.asarray(state.counts)
    fin_coef = np.isfinite(coef)
    fin_sq = np.isfinite(sqnorm)
    fin_ct = np.isfinite(counts)
    patched = int((~fin_coef).sum() + (~fin_sq).sum() + (~fin_ct).sum())
    dead = ~np.any(fin_coef & (coef != 0), axis=1)
    if patched == 0 and not dead.any():
        return carry, CarryGuardReport(0, 0)     # identity: same object
    coef = np.where(fin_coef, coef, 0.0).astype(coef.dtype)
    sqnorm = np.where(fin_sq, sqnorm, 0.0).astype(sqnorm.dtype)
    counts = np.where(fin_ct, counts, 0.0).astype(counts.dtype)
    idx = np.array(state.idx, copy=True)
    head = np.array(state.head, copy=True)
    reseeded = 0
    if dead.any() and x is not None and kernel is not None:
        from repro.core.kernel_fns import kernel_diag

        xh = np.asarray(x)
        ok_rows = np.flatnonzero(np.isfinite(xh).all(axis=1))
        step = int(np.asarray(state.step))
        for j in np.flatnonzero(dead):
            if ok_rows.size == 0:
                break
            pick = int(ok_rows[int(np.random.default_rng(
                (int(seed), step, int(j))).integers(0, ok_rows.size))])
            idx[j] = 0
            idx[j, 0] = pick
            coef[j] = 0.0
            coef[j, 0] = 1.0
            head[j] = 1
            sqnorm[j] = float(np.asarray(
                kernel_diag(kernel, xh[pick:pick + 1]))[0])
            counts[j] = 0.0
            reseeded += 1
    guarded = carry._replace(state=state._replace(
        idx=idx, coef=coef, head=head, sqnorm=sqnorm, counts=counts))
    return guarded, CarryGuardReport(patched, reseeded)


# ---------------------------------------------------------------------------
# Cross-executor compiled-program cache (the donation / program-cache axis).
#
# Executors cache their compiled programs on the instance, but the
# instance is rebuilt whenever a plan is re-resolved (a fresh KernelKMeans
# per fit, the legacy shims, plan signature changes) — and every rebuild
# used to re-bind (re-trace, re-compile) programs whose closure is
# IDENTICAL: same Algorithm-2 statics, same kernel values, same mesh, same
# donated-argnum signature.  This registry keys compiled programs on
# exactly that closure signature, so repeated ``fit`` / ``partial_fit`` on
# same-shape data reuses ONE executable across executor instances.
# Kernels with large array leaves (Precomputed grams, cached kernels) are
# not value-keyed — id() reuse after GC could alias two different datasets
# — so those programs stay instance-local, the historical behaviour.
#
# ``program_builds()`` counts actual program constructions (the
# compile-counter hook tests/test_fused_step.py regresses against).

_PROGRAM_CACHE: dict = {}        # insertion-ordered (LRU via re-insert)
_PROGRAM_CACHE_MAX = 128         # distinct (config, kernel, mesh) closures
_PROGRAM_BUILDS = [0]

# Loop-core entries: bumped whenever a fit actually runs (host driver) or
# traces (device driver) through this module — the structural-guard hook
# (tests/test_loop_guard.py) asserting every registered solver routes
# through the loop core rather than owning a private fit loop.
_LOOP_RUNS = [0]


def loop_runs() -> int:
    """How many times a fit has entered a loop-core driver (host runs +
    device-driver traces) since import — monotone, like
    :func:`program_builds`."""
    return _LOOP_RUNS[0]


def program_builds() -> int:
    """How many compiled fit programs have been BUILT (not reused) since
    import — a monotone counter; snapshot it around a fit to assert the
    fit re-bound nothing."""
    return _PROGRAM_BUILDS[0]


# The fit path's names in a profiler trace, all of them here.  Host spans
# (``span``): ``kkm.fit`` around ``KernelKMeans.fit``, ``kkm.init`` around
# the init draw, ``kkm.run`` around the compiled program's lookup and
# dispatch.  Device stages (``scope``, trace-time metadata only): the
# on-device loop, the batch draw, and the Algorithm-2 step's gathers,
# assignment pass, rates and ring append, <C_j, C_j> recompute, objective
# pass, and the streaming kernel's padding; ``kkm.init`` also names the
# init's device ops, and ``kkm.gram`` (span and stage) the k-nn Gram build
# of ``repro.data.graph_kernels``.  A device op's stage is the innermost
# ``kkm.*`` component of its scope path.
STAGES = ("kkm.fit", "kkm.init", "kkm.run", "kkm.loop", "kkm.sample",
          "kkm.gather", "kkm.assign", "kkm.update", "kkm.sqnorm",
          "kkm.objective", "kkm.pad", "kkm.gram")


def span(name: str):
    """A host span on the profiler's clock (``TraceAnnotation``); under a
    microsecond per span when no trace is being taken."""
    if name not in STAGES:
        raise ValueError(f"unknown fit-path span {name!r}")
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device stage: names every op traced inside it
    (``jax.named_scope``); changes no value and costs nothing at run
    time."""
    if name not in STAGES:
        raise ValueError(f"unknown fit-path stage {name!r}")
    return jax.named_scope(name)


def clear_program_cache() -> None:
    _PROGRAM_CACHE.clear()


def _cache_put(key, prog) -> None:
    """Insert with LRU eviction: the registry is process-lifetime, and
    keys carry dataset-dependent parts (padded sizes, max_iters), so a
    long-running service fitting many shapes must not pin every
    executable it ever compiled.  Evicted programs stay alive as long as
    some executor instance still holds them (``self._programs``)."""
    _PROGRAM_CACHE[key] = prog
    while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
        _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))


def _cache_get(key):
    prog = _PROGRAM_CACHE.pop(key, None)
    if prog is not None:
        _PROGRAM_CACHE[key] = prog        # refresh recency
    return prog


def _kernel_sig(kernel):
    """Value signature of a kernel pytree, or None when any leaf is too
    large to key by value (then programs must stay instance-local)."""
    leaves, treedef = jax.tree_util.tree_flatten(kernel)
    sig = []
    for leaf in leaves:
        if np.size(leaf) > 64:          # sized before any copy to the host
            return None
        a = np.asarray(leaf)
        sig.append((a.dtype.str, a.shape, a.tobytes()))
    return (treedef, tuple(sig))


def lookup_program(programs: dict, owner: str, key, build, kernel=None,
                   kernel_free: bool = False):
    """Compiled-program lookup: the instance cache ``programs`` first,
    then the cross-executor registry above.  ``key`` must capture the
    FULL closure signature minus the kernel — loop statics, mesh/axes,
    and the donated-argnum signature.  The kernel is value-keyed when its
    leaves are small; ``kernel_free`` marks programs that take the kernel
    as a traced ARGUMENT (nothing kernel-shaped in the closure), which
    share unconditionally."""
    prog = programs.get(key)
    if prog is None:
        ksig = True if kernel_free else _kernel_sig(kernel)
        if ksig is None:
            _PROGRAM_BUILDS[0] += 1
            prog = build()
        else:
            gkey = (owner, key, ksig)
            prog = _cache_get(gkey)
            if prog is None:
                _PROGRAM_BUILDS[0] += 1
                prog = build()
                _cache_put(gkey, prog)
        programs[key] = prog
    return prog


def _x_keyed_run(runs: dict, key, x_real, build):
    """Compile-cache lookup for programs that CLOSE OVER a dataset
    (``x_real``): the entry is valid only for that exact array object,
    never merely for its shape — refitting on new same-shaped data must
    rebuild (regression: stale coordinates baked in as jit constants)."""
    entry = runs.get(key)
    if entry is not None and entry[0] is x_real:
        return entry[1]
    run = build()
    runs[key] = (x_real, run)
    return run


def loop_config(mb, early_stop: bool, max_iters=None):
    """The MBConfig a jitted early-stopped loop should run with:
    ``early_stop=False`` lowers to an epsilon no improvement can undercut
    (the ``run_early_stopped`` condition is baked into the compiled loop,
    unlike the host loop's python check)."""
    if max_iters is not None:
        mb = mb._replace(max_iters=max_iters)
    if not early_stop:
        mb = mb._replace(epsilon=float("-inf"))
    return mb


# ---------------------------------------------------------------------------
# The precision axis (SolverConfig ``precision`` / MBConfig
# ``compute_dtype``), registered once for every step builder.


class PrecisionPlan(NamedTuple):
    """Resolved kernel-eval precision for one (kernel, config) point.

    ``cdt=None`` is the IDENTITY: both cast helpers are no-ops and the
    emitted program is the historical f32 one, bit-for-bit.  With
    ``cdt=bfloat16`` the COORDINATES entering kernel evaluations are cast
    to bf16 (MXU-native) while coefficients, argmin carries and every
    accumulation stay f32.  Index-data kernels (Precomputed / cached)
    carry row ids as data — a cast would corrupt the gather keys — so
    they always resolve to the identity regardless of the config."""

    cdt: Any                # jnp.bfloat16 or None (None = identity)
    index_data: bool        # kernel rows are gather keys, never cast
    tag: str                # "bf16" | "f32" (the fused kernels' static)

    def cast(self, v):
        """Kernel-eval compute-dtype cast (the step builders' ``_c``)."""
        return v.astype(self.cdt) if self.cdt is not None else v

    def f32(self, v):
        """Back to f32 for accumulation (the step builders' ``_f32``)."""
        return v.astype(jnp.float32) if self.cdt is not None else v


def precision_plan(kernel, cfg) -> PrecisionPlan:
    """THE precision-axis registration site: every step builder
    (``minibatch.make_step``, ``minibatch._make_fused_step``,
    ``distributed._make_local_step``) resolves its compute dtype here, so
    a new precision mode lands in one place."""
    from repro.core.kernel_fns import is_index_data

    index_data = is_index_data(kernel)
    cdt = jnp.bfloat16 if (cfg.compute_dtype == "bfloat16"
                           and not index_data) else None
    return PrecisionPlan(cdt=cdt, index_data=index_data,
                         tag="bf16" if cdt is not None else "f32")


# ---------------------------------------------------------------------------
# The compress axis (landmark projection cadence), registered once.


def compress_hook(step, kernel, cfg, *, local: bool = False,
                  model_axis: str = "model"):
    """THE compress-axis registration site: wrap a step so every
    ``cfg.compress.every``-th iteration ends with an in-place landmark
    projection (:mod:`repro.landmark.compress`).  ``compress=None`` (and
    ``every=0``, the round-cadence-only mode) return ``step`` itself —
    the emitted program is the historical one, bit-for-bit (the
    ``cdt=None`` identity convention).  ``local=True`` wraps the
    shard-local step body instead (model-sharded centers; selection keys
    fold in the global center id via the model-axis index)."""
    spec = cfg.compress
    if spec is None or spec.every <= 0:
        return step
    from repro.landmark.compress import wrap_local_step, wrap_step

    if local:
        return wrap_local_step(step, kernel, spec, model_axis)
    return wrap_step(step, kernel, spec)


# ---------------------------------------------------------------------------
# Driver 1: the host-driven early-stopped loop (THE prefetch site).


def drive_fit_loop(dispatch, draw, cursor, *, max_iters: int,
                   epsilon: float, early_stop: bool = True,
                   prefetch: bool = False, step0: int = 0,
                   stage=jax.device_put):
    """The host-driven early-stopped fit loop — the single driver behind
    every non-jit plan (single/precomputed/lru via
    ``minibatch.host_fit_loop``; the sharded stream plan via
    ``distributed._fit_distributed_impl``).

    Per iteration: ``draw(cursor, i) -> (cursor', item)`` produces the
    next batch (``item=None`` ends the loop — an exhausted stream);
    ``dispatch(item) -> StepInfo`` issues the device step (asynchronous —
    state threads through the adapter's closure); the loop then blocks on
    ``float(info.improvement)`` and stops early when it drops below
    ``epsilon``.  ``step0`` offsets the iteration counter so
    ``partial_fit`` resumption continues both the nested-sampler schedule
    and the history numbering.  Returns ``(history, cursor)``.

    ``prefetch``: one-deep pipeline — iteration i+1's item is drawn (and
    staged on device via ``stage``) after DISPATCHING step i but before
    blocking on its improvement, so sampling/transfer overlaps the device
    step.  The drawn values and the returned cursor are identical to the
    blocking path: an early stop discards the prefetched item without
    advancing the cursor (key-stream draws consume nothing; a caller-owned
    iterator may observably have yielded one extra item).  Results are
    bit-identical either way (tested)."""
    _LOOP_RUNS[0] += 1
    history = []
    end = step0 + max_iters
    pending = None
    for i in range(step0, end):
        cur, item = pending if pending is not None else draw(cursor, i)
        pending = None
        if item is None:
            break
        info = dispatch(item)                 # async dispatch
        if prefetch and i + 1 < end:
            nxt_cur, nxt = draw(cur, i + 1)   # overlaps the device step
            if nxt is not None:
                pending = (nxt_cur, stage(nxt))
        imp = float(info.improvement)         # host sync point
        cursor = cur
        history.append(dict(step=i, f_before=float(info.f_before),
                            f_after=float(info.f_after), improvement=imp))
        if early_stop and imp < epsilon:
            break
    return history, cursor


# ---------------------------------------------------------------------------
# Driver 2: the on-device early-stopped loop (one compiled while_loop).


def run_early_stopped_keyed(cfg, step_with_key, state, key: jax.Array):
    """The paper's on-device early-stopped driver, shared by every jitted
    fit path (the single jit plan, the multi-restart engine, the sharded
    while_loop): while i < max_iters and the last improvement >= epsilon,
    advance the unified batch-key stream
    (:func:`repro.api.keys.next_batch_key`) and apply
    ``step_with_key(state, kb) -> (state, improvement)``.
    Returns (state, iters, key) — the carried key resumes the stream
    exactly where the loop stopped (``KernelKMeans.partial_fit``)."""
    _LOOP_RUNS[0] += 1    # bumped at trace time (the device driver)

    def cond(carry):
        _, _, i, imp = carry
        return (i < cfg.max_iters) & (imp >= cfg.epsilon)

    def body(carry):
        state, key, i, _ = carry
        with scope("kkm.sample"):
            key, kb = api_keys.next_batch_key(key)
        state, imp = step_with_key(state, kb)
        return state, key, i + 1, imp

    init_carry = (state, key, jnp.zeros((), jnp.int32),
                  jnp.full((), jnp.inf, jnp.float32))
    with scope("kkm.loop"):
        state, key, iters, _ = jax.lax.while_loop(cond, body, init_carry)
    return state, iters, key


def run_early_stopped(cfg, step_with_key, state, key: jax.Array):
    """:func:`run_early_stopped_keyed` without the carried key — the
    historical signature, kept for callers that never resume."""
    state, iters, _ = run_early_stopped_keyed(cfg, step_with_key, state, key)
    return state, iters


# ---------------------------------------------------------------------------
# LoopSpec: the declarative lowering description every executor supplies.


class LoopSpec(NamedTuple):
    """How one solver plan lowers onto the fit-loop core — exactly the
    parts that genuinely differ between families.  Everything else (the
    stage sequence, early stop, prefetch, precision/compress hooks,
    program caching, carry) is the shared core above.  Rendered by
    :func:`stages` / ``KernelKMeans.explain()``."""

    lowering: str           # registered solver name
    driver: str             # 'host' | 'device' | 'stream'
    sampler: str            # how batches are drawn
    step: str               # the step body this lowering supplies
    placement: str          # mesh / sharding description
    donation: tuple         # donated argnums of the main fit program
    hooks: tuple            # active cross-cutting axes (subset of
    #                         'prefetch', 'precision:bf16', 'compress')


_DRIVERS = {
    "host": "host-driven python loop (drive_fit_loop; per-step "
            "improvement sync)",
    "device": "one compiled lax.while_loop (run_early_stopped_keyed; "
              "zero per-step host sync)",
    "stream": "host iterator loop (drive_fit_loop over a batch stream)",
}


def stages(spec: LoopSpec) -> list:
    """The canonical stage sequence of ``spec``'s fit loop, specialized
    with the lowering's own sampler/step/hooks — what
    ``KernelKMeans.explain()`` and ``serve --dry-run`` print."""
    if spec.driver not in _DRIVERS:
        raise ValueError(f"unknown driver {spec.driver!r} "
                         f"(expected one of {sorted(_DRIVERS)})")
    out = ["derive keys (repro.api.keys: one audited derivation tree)",
           f"sample batch [{spec.sampler}]"]
    if "prefetch" in spec.hooks:
        out.append("prefetch next batch (one-deep pipeline, overlaps the "
                   "device step)")
    step = f"step body [{spec.step}]"
    if "precision:bf16" in spec.hooks:
        step += " @ bf16 kernel evals, f32 accumulation"
    out.append(step)
    if "compress" in spec.hooks:
        out.append("compress cadence hook (in-loop landmark projection)")
    out.append(f"early stop via {_DRIVERS[spec.driver]}")
    out.append("carry/telemetry (FitCarry resume key + step history)")
    return out
