"""``KernelKMeans`` — sklearn-style estimator over the solver-plan layer.

    from repro.api import KernelKMeans, SolverConfig

    est = KernelKMeans(SolverConfig(k=8, kernel="rbf",
                                    kernel_params={"kappa": 2.0},
                                    cache="auto", restarts=4))
    est.fit(x, key=0)
    labels = est.predict(xq)
    est.save("centers.npz"); served = KernelKMeans.load("centers.npz")

One ``fit`` for every execution point (cache x distribution x restarts x
sampler x jit); the estimator resolves the config to a plan
(:func:`repro.api.plan.resolve_plan`), caches the executor — and with it
the compiled programs — across fits, and owns the serving surface
(``predict`` / ``transform`` / ``score``) plus the ``save``/``load``
state round-trip for serving processes.
"""
from __future__ import annotations

import io
import json
import struct
import zipfile
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import keys as api_keys
from repro.api.config import SolverConfig, field_names
from repro.api.executors import (
    _assign, _distances, carry_of, outcome_from_carry, FitCarry,
)
from repro.core.kernel_fns import kernel_spec, make_kernel
from repro.core.loop import span
from repro.core.state import CenterState

# SolverConfig fields that are JSON-serializable as-is (everything except
# the kernel spec, which save() lowers to (name, params)).
_JSON_FIELDS = tuple(f for f in field_names()
                     if f not in ("kernel", "kernel_params"))

# format-3 integrity footer: the npz payload is followed by 8 bytes —
# a 4-byte magic + the CRC32 of the payload.  Disk corruption anywhere
# in the file (payload OR footer) fails verification; the zip container
# alone catches truncation but not in-place bit flips.
_CRC_MAGIC = b"KKC3"
_CRC_FOOTER = struct.Struct("<4sI")


class SnapshotIntegrityError(RuntimeError):
    """Snapshot file failed its integrity check (CRC mismatch, truncated
    or undecodable container) — the bytes on disk are not the bytes that
    were saved.  Callers must treat the file as garbage: quarantine and
    fall back, never serve from it."""


def _verified_payload(path: str) -> bytes:
    """The npz payload of ``path`` with its format-3 CRC footer verified
    and stripped.  Legacy files (format 1/2, no footer) pass through
    whole — their container parse is their only integrity check."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) >= _CRC_FOOTER.size:
        magic, crc = _CRC_FOOTER.unpack(raw[-_CRC_FOOTER.size:])
        if magic == _CRC_MAGIC:
            payload = raw[:-_CRC_FOOTER.size]
            if zlib.crc32(payload) != crc:
                raise SnapshotIntegrityError(
                    f"CRC mismatch in {path}: stored {crc:#010x}, "
                    f"computed {zlib.crc32(payload):#010x}")
            return payload
    return raw


class KernelKMeans:
    """Mini-batch kernel k-means estimator (the paper's Algorithm 2 under
    every execution strategy the repo implements).

    Parameters: a :class:`SolverConfig` (or field overrides as kwargs) and
    an optional ``mesh`` for the sharded / restart-sharded plans.

    Fitted attributes: ``state_`` (truncated-center state), ``history_``
    (host-driven plans), ``iters_``, ``cache_`` (tile cache(s), cached
    plans), ``result_`` (per-restart ``EngineResult``, multi-restart
    plans), ``plan_`` (the resolved :class:`repro.api.plan.Plan`).
    """

    def __init__(self, config: Optional[SolverConfig] = None, *,
                 mesh=None, **overrides):
        if config is None:
            config = SolverConfig(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        self.config = config
        self.mesh = mesh
        self.plan_ = None
        self._plan_sig = None
        self._carry_solver = None  # plan name a load()ed carry came from
        self._outcome = None
        self._x = None
        self._serving = None      # (kernel, sup, coef, sqnorm) after load()
        self.state_ = None
        self.history_ = None
        self.iters_ = None
        self.cache_ = None
        self.result_ = None
        # landmark-compression counters (docs/compression.md): cumulative
        # across the estimator's life, survive save()/load()
        self._compress_stats = {"compressions": 0, "m": None,
                                "last_drift": None, "ratio": None}

    # ------------------------------------------------------------- plans
    def plan_for(self, n: int):
        """Resolve (and cache) the execution plan for an n-row dataset.
        The executor — and the compiled programs it holds — is reused
        across fits as long as the resolved execution point is stable."""
        from repro.api.plan import resolve_plan

        resolved = self.config.resolve(n=n, mesh=self.mesh)
        sig = (resolved.cache, resolved.distribution, resolved.restarts,
               resolved.sampler, resolved.jit)
        if self.plan_ is None or sig != self._plan_sig:
            self.plan_ = resolve_plan(self.config, n=n, mesh=self.mesh)
            self._plan_sig = sig
        return self.plan_

    # --------------------------------------------------------------- fit
    def fit(self, X, key: Any = 0, *, init_idx=None, sample_weight=None):
        """Fit on ``(n, d)`` data (or the ``(n, 1)`` index view of a
        precomputed kernel).  ``key``: int seed or JAX PRNG key — the
        estimator derives init/fit keys through :mod:`repro.api.keys`, so
        the same seed draws the same batch sequence on every
        single-restart plan."""
        with span("kkm.fit"):
            X = jnp.asarray(X)
            key = api_keys.as_key(key)
            plan = self.plan_for(X.shape[0])
            out = plan.executor.fit(X, key, init_idx=init_idx,
                                    sample_weight=sample_weight)
            self._set_fitted(X, out)
        return self

    def partial_fit(self, X, key: Any = 0, *, iters: Optional[int] = None):
        """Continue (or start) fitting for ``iters`` more iterations
        (default ``config.max_iters``), resuming the batch-key stream
        exactly where the previous call stopped — ``fit(max_iters=a+b)``
        and ``fit(max_iters=a); partial_fit(iters=b)`` draw identical
        batches.  Single-restart, single-device plans only.

        .. note:: on the compiled (``jit=True``) plan the resume program
           DONATES the previous fitted state's buffers (steady-state
           partial_fit chains allocate nothing per call) — a reference
           to the pre-call ``state_`` is dead afterwards; snapshot it
           with ``jax.device_get`` / ``np.asarray`` first if you need
           the before/after pair."""
        X = jnp.asarray(X)
        iters = iters if iters is not None else self.config.max_iters
        if self._outcome is None:
            plan = self.plan_for(X.shape[0])
            if not plan.executor.supports_partial_fit:
                raise NotImplementedError(
                    f"plan {plan.name!r} does not support partial_fit "
                    "(use restarts=1, distribution='single', "
                    "cache='none')")
            out = plan.executor.fit(X, api_keys.as_key(key),
                                    max_iters=iters)
            self._set_fitted(X, out)
            return self
        # A load()ed estimator carries a resumable outcome but no plan
        # yet.  Resume on the SAVED plan, not whatever ``auto`` axes would
        # resolve to for the resume dataset's size — otherwise e.g. a
        # cache='auto' fit on large data (plan 'single') resumed on small
        # data would re-resolve to 'single_precomputed' and refuse.
        if self.plan_ is None and self._carry_solver is not None:
            from repro.api.plan import resolve_plan

            self.plan_ = resolve_plan(self.config, n=X.shape[0],
                                      mesh=self.mesh,
                                      solver=self._carry_solver)
            # a sentinel signature no plan_for() resolution can equal: a
            # later full fit() must re-resolve through the registry
            # instead of inheriting the carry-forced executor
            self._plan_sig = ("carry", self._carry_solver)
        plan = self.plan_ if self.plan_ is not None \
            else self.plan_for(X.shape[0])
        if not plan.executor.supports_partial_fit:
            raise NotImplementedError(
                f"plan {plan.name!r} does not support partial_fit")
        out = plan.executor.resume(X, self._outcome, iters)
        if self.history_ is not None and out.history is not None:
            out.history = self.history_ + out.history
        self._set_fitted(X, out)
        return self

    def _set_fitted(self, X, out):
        self._x = X
        self._outcome = out
        self._serving = None
        self.state_ = out.state
        self.history_ = out.history
        self.iters_ = out.iters
        self.cache_ = out.cache if out.cache is not None else out.caches
        self.result_ = out.engine

    # ----------------------------------------------------------- serving
    def _serving_tuple(self):
        if self._serving is not None:
            return self._serving
        if self._outcome is None:
            raise RuntimeError("fit() (or load()) before serving")
        return self.plan_.executor.serving_tuple(self._outcome, self._x)

    def predict(self, X, chunk: int = 4096):
        """Nearest-center labels (nq,) for coordinate queries."""
        X = jnp.asarray(X)
        if self._serving is None and self._outcome is not None:
            return self.plan_.executor.predict(self._outcome, self._x, X,
                                               chunk=chunk)
        kern, sup, coef, sqnorm = self._serving_tuple()
        return _assign(kern, coef, sqnorm, sup, X, chunk)

    def transform(self, X, chunk: int = 4096):
        """Feature-space distances d(x, C_j), (nq, k) — the
        cluster-distance embedding."""
        kern, sup, coef, sqnorm = self._serving_tuple()
        return _distances(kern, coef, sqnorm, sup, jnp.asarray(X), chunk)

    def score(self, X) -> float:
        """Negative clustering objective (mean min squared feature-space
        distance) — higher is better, sklearn-style."""
        d = self.transform(X)
        return -float(jnp.mean(jnp.min(d, axis=1)))

    def fit_predict(self, X, key: Any = 0, **kw):
        return self.fit(X, key, **kw).predict(X)

    # ----------------------------------------------------------- explain
    def explain(self, n: Optional[int] = None, *, d: int = 16,
                deep: bool = False) -> dict:
        """The resolved execution plan WITHOUT fitting anything: the
        registered solver it lowers to, the resolved config axes, the
        plan's :class:`repro.core.loop.LoopSpec` (sampler / step body /
        placement / donation / active hooks) and the canonical fit-loop
        stage sequence.  ``serve --dry-run`` prints exactly this.

        ``n``: dataset rows to resolve the plan for (the ``auto`` axes are
        size-dependent); defaults to the fitted dataset's size, else 4096.
        ``deep=True`` additionally ``.lower().compile()``'s the plain
        single-device step on ``(n, d)`` ShapeDtypeStructs and attaches
        its HLO memory/cost/collective analysis
        (:func:`repro.launch.analysis.analyze_compiled`)."""
        from repro.core import loop as loop_lib

        if n is None:
            n = self._x.shape[0] if self._x is not None else 4096
        plan = self.plan_for(n)
        resolved = self.config.resolve(n=n, mesh=self.mesh)
        spec = plan.executor.loop_spec()
        out = {
            "plan": plan.name,
            "n": int(n),
            "config": {f: getattr(resolved, f) for f in
                       ("cache", "distribution", "restarts", "sampler",
                        "jit", "step", "precision", "prefetch",
                        "compute_dtype")},
            "lowering": dict(spec._asdict()),
            "stages": loop_lib.stages(spec),
        }
        if deep:
            out["compiled_step"] = self._explain_deep(plan, n, d)
        return out

    def _explain_deep(self, plan, n: int, d: int) -> dict:
        """HLO analysis of the representative step program.  Only the
        plain coordinate-kernel step is analyzable without a dataset in
        the closure (precomputed/cached/sharded programs are built inside
        ``fit`` around the actual Gram / tile caches / mesh placement)."""
        if plan.name != "single":
            return {"note": f"plan {plan.name!r} builds its step program "
                            "inside fit (dataset / tile-cache / mesh "
                            "closure); fit once and inspect "
                            "program_builds() or benchmarks/run.py "
                            "instead"}
        from repro.core.minibatch import make_step
        from repro.core.state import init_state, window_size
        from repro.launch.analysis import analyze_compiled

        ex = plan.executor
        mb = ex.mb
        w = window_size(mb.batch_size, mb.tau)
        x_s = jax.ShapeDtypeStruct((n, d), jnp.float32)
        idx_s = jax.ShapeDtypeStruct((mb.k,), jnp.int32)
        state_s = jax.eval_shape(
            lambda x, i: init_state(x, i, ex.kernel, w), x_s, idx_s)
        b_s = jax.ShapeDtypeStruct((mb.batch_size,), jnp.int32)
        compiled = jax.jit(make_step(ex.kernel, mb)).lower(
            state_s, x_s, b_s).compile()
        return analyze_compiled(compiled)

    # ----------------------------------------------- landmark compression
    def compress(self, m: Optional[int] = None,
                 selector: Optional[str] = None,
                 jitter: Optional[float] = None) -> "KernelKMeans":
        """Project the SERVING representation onto ``m`` landmark rows per
        center (:class:`repro.landmark.serving.CompressedKernelCenters`):
        predict/transform/score afterwards cost O(k*m) per query and never
        touch the original support window.  Defaults come from the
        ``compress`` config axis.  The resumable fit carry is untouched —
        ``partial_fit`` keeps full fidelity and re-derives fresh serving
        state (compress again after it for bounded serving; the service
        Learner does exactly that each round).  Landmark selection is
        keyed by the fit step counter, so a crash-recovered learner
        reproduces the same compressed model bit-for-bit."""
        from repro.landmark.compress import CompressSpec
        from repro.landmark.serving import CompressedKernelCenters

        spec = self.config.compress_spec()
        if spec is None:
            spec = CompressSpec()
        if m is not None:
            spec = spec._replace(m=int(m))
        if selector is not None:
            spec = spec._replace(selector=selector)
        if jitter is not None:
            spec = spec._replace(jitter=float(jitter))
        kern, sup, coef, sqnorm = self._serving_tuple()
        k, w = coef.shape
        if spec.m >= w:
            return self   # already at/below the target support size
        step = self.state_.step if self.state_ is not None else \
            self._compress_stats["compressions"]
        ckc, info = CompressedKernelCenters.from_serving(
            kern, sup, coef, sqnorm, spec=spec._replace(every=0), step=step)
        self._serving = ckc.serving_tuple()
        st = self._compress_stats
        st["compressions"] += 1
        st["m"] = spec.m
        st["last_drift"] = float(info.drift_bound)
        st["ratio"] = spec.m / w
        return self

    def support_stats(self) -> Optional[dict]:
        """Live serving-support telemetry (present even with
        ``compress="off"``): total support rows, active (coef != 0) rows,
        the per-center window W, and the compression counters.  ``None``
        before fit()/load()."""
        if self._serving is None and self._outcome is None:
            return None
        _, sup, coef, _ = self._serving_tuple()
        coef = np.asarray(coef)
        k, w = coef.shape
        return {"rows": int(sup.shape[0]), "active":
                int(np.count_nonzero(coef)), "window": int(w), "k": int(k),
                **self._compress_stats}

    # ---------------------------------------------------- snapshot hooks
    # The serving split (repro.service) drives a long-lived estimator from
    # learner threads: it needs the resumable carry as HOST arrays (the
    # compiled resume program donates the device buffers, so a device-side
    # reference dies on the next partial_fit) and an in-place restore that
    # keeps the resolved plan — these three hooks are that surface.

    def snapshot_carry(self):
        """The current :class:`FitCarry` with every array leaf
        materialized to host numpy — safe to hold across donating
        ``partial_fit`` calls, to checkpoint, or to hand to another
        thread.  ``None`` when the fitted plan is not resumable."""
        carry = carry_of(self._outcome)
        if carry is None:
            return None
        return FitCarry(
            state=jax.tree.map(lambda a: np.asarray(a), carry.state),
            key=np.asarray(carry.key), steps=carry.steps,
            iters=carry.iters)

    def restore_carry(self, carry: FitCarry) -> "KernelKMeans":
        """Adopt ``carry`` as the resume point for the next
        ``partial_fit`` (the inverse of :meth:`snapshot_carry`); the
        resolved plan and compiled programs are kept."""
        self._outcome = outcome_from_carry(
            FitCarry(state=jax.tree_util.tree_map(jnp.asarray, carry.state),
                     key=jnp.asarray(carry.key), steps=carry.steps,
                     iters=carry.iters))
        self._serving = None
        self.state_ = self._outcome.state
        self.iters_ = self._outcome.iters
        self.history_ = None
        return self

    def save_atomic(self, path: str) -> str:
        """:meth:`save` through a same-directory temp file +
        ``os.replace`` — a concurrent reader (a serving actor) sees either
        the complete old file or the complete new one, never a torn
        write."""
        import os

        tmp = f"{path}.tmp.{os.getpid()}"
        self.save(tmp)
        os.replace(tmp, path)
        return path

    # -------------------------------------------------------- save / load
    def save(self, path: str) -> str:
        """Serialize the serving state (support coordinates, coefficients,
        center norms) plus the config to an ``.npz``.  Works for every
        plan whose kernel has a registry spec (``kernel_spec``) — cached /
        precomputed / sharded states are lowered to base-kernel support
        coordinates first, so a served prediction needs no cache, Gram or
        mesh.

        Plans that support ``partial_fit`` additionally round-trip their
        full :class:`repro.api.executors.FitCarry` — the center state,
        the carried PRNG fit key and the step cursor — so
        ``fit(a); save; load; partial_fit(b)`` draws exactly the batches
        ``fit(a); partial_fit(b)`` would have drawn (bit-identical
        states)."""
        kern, sup, coef, sqnorm = self._serving_tuple()
        name, params = kernel_spec(kern)
        # format 2 (the compressed-representation bump): adds "format" and
        # "compress" meta keys; the serving arrays may be a landmark-
        # compressed (k*m)-row representation while the carry arrays stay
        # the full resumable window.
        # format 3 (the integrity bump): the same npz payload followed by
        # an 8-byte CRC32 footer so disk corruption is DETECTED at load
        # time (SnapshotIntegrityError) instead of silently decoding to
        # garbage centers.  load() still accepts format-1 files (no
        # "format" key) and footer-less format-2 files unchanged — see
        # tests/test_save_load_skew.py.
        meta = {"format": 3, "kernel": name, "kernel_params": params,
                "config": {f: getattr(self.config, f)
                           for f in _JSON_FIELDS},
                "compress": self._compress_stats}
        arrays = dict(sup=np.asarray(sup), coef=np.asarray(coef),
                      sqnorm=np.asarray(sqnorm))
        # resumable iff the plan supports partial_fit; an estimator that
        # was itself load()ed (no plan yet) only holds an outcome when its
        # saved carry was resumable, so it keeps round-tripping
        resumable = (self.plan_.executor.supports_partial_fit
                     if self.plan_ is not None else self._x is None)
        carry = carry_of(self._outcome) if resumable else None
        if carry is not None and isinstance(carry.state, CenterState):
            for f, v in zip(carry.state._fields, carry.state):
                arrays[f"carry_{f}"] = np.asarray(v)
            arrays["carry_key"] = np.asarray(carry.key)
            meta["carry"] = {"steps": carry.steps, "iters": carry.iters,
                             "solver": (self.plan_.name
                                        if self.plan_ is not None
                                        else self._carry_solver)}
        buf = io.BytesIO()
        np.savez(buf, meta=np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8), **arrays)
        payload = buf.getvalue()
        with open(path, "wb") as f:
            f.write(payload)
            f.write(_CRC_FOOTER.pack(_CRC_MAGIC, zlib.crc32(payload)))
        return path

    @classmethod
    def load(cls, path: str) -> "KernelKMeans":
        """Rebuild a serving estimator (``predict`` / ``transform`` /
        ``score``).  When the file carries a :class:`FitCarry` (saved by a
        ``partial_fit``-capable plan), the estimator is also RESUMABLE:
        ``partial_fit(X)`` continues the batch-key stream exactly where
        the saved fit stopped."""
        payload = _verified_payload(path)
        try:
            with np.load(io.BytesIO(payload)) as data:
                meta = json.loads(bytes(data["meta"]).decode())
                sup = jnp.asarray(data["sup"])
                coef = jnp.asarray(data["coef"])
                sqnorm = jnp.asarray(data["sqnorm"])
                carry = None
                if "carry_key" in data:
                    state = CenterState(*(jnp.asarray(data[f"carry_{f}"])
                                          for f in CenterState._fields))
                    cmeta = meta["carry"]
                    carry = FitCarry(state=state,
                                     key=jnp.asarray(data["carry_key"]),
                                     steps=cmeta["steps"],
                                     iters=cmeta["iters"])
        except (zipfile.BadZipFile, KeyError, OSError,
                json.JSONDecodeError, EOFError, ValueError) as e:
            # legacy (footer-less) files have no CRC; any undecodable
            # container — truncated write, bit flip inside a zip member —
            # surfaces as ONE clean error class, never garbage centers
            raise SnapshotIntegrityError(
                f"undecodable snapshot {path}: {e}") from e
        fmt = meta.get("format", 1)   # pre-compression files carry no key
        if fmt > 3:
            raise ValueError(f"snapshot format {fmt} is newer than this "
                             "build understands (<= 3)")
        cfg_dict = dict(meta["config"])
        cfg_dict["kernel"] = meta["kernel"]
        cfg_dict["kernel_params"] = meta["kernel_params"]
        est = cls(SolverConfig(**cfg_dict))
        if fmt >= 2 and meta.get("compress"):
            est._compress_stats.update(meta["compress"])
        est._serving = (make_kernel(meta["kernel"],
                                    **meta["kernel_params"]),
                        sup, coef, sqnorm)
        if carry is not None:
            est._outcome = outcome_from_carry(carry)
            est._carry_solver = meta["carry"].get("solver")
            est.state_ = est._outcome.state
            est.iters_ = est._outcome.iters
        return est
