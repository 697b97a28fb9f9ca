"""The learner half of the serving split: continuous ``partial_fit`` over
the ingest buffer, with versioned snapshot publishing and crash recovery.

One learner round = (advance the ingest buffer one push) -> (resume the
estimator for ``iters_per_round`` mini-batch iterations on the buffer's
``(capacity, d)`` snapshot) -> (every ``publish_every`` rounds, atomically
publish the full estimator snapshot — serving tuple + resumable
:class:`FitCarry` — as version ``round``).

Why this is deterministic (and therefore recoverable): the buffer content
at round ``t`` is a pure function of ``(ingest seed, t)`` given the
deterministic arrival stream (:mod:`repro.service.buffer`), and the batch
indices drawn inside ``partial_fit`` are a pure function of the carried
PRNG fit key — which rides the published carry (the unified
:class:`repro.core.loop.FitCarry` every lowering threads through the
fit-loop core, so the learner resumes identically on whichever driver
the resolved plan uses — docs/architecture.md).  So
:func:`repro.train.resilience.run_resilient` can crash anywhere, restore
the last PUBLISHED snapshot (the snapshot is the checkpoint —
``SnapshotStore.as_checkpointer``), rewind the buffer by replaying the
stream, and converge to a carry BIT-IDENTICAL to an uninterrupted run
(tests/test_service.py, 8-virtual-device lane).

The fixed buffer capacity keeps the resume program's shapes constant, so
the PR-5 cross-executor program cache compiles it once —
``program_builds()`` stays flat across rounds (gated by
``tests/test_service.py::test_learner_program_builds_flat_after_round_one``).
The per-round early stop (``epsilon`` on the round's improvement) is the
mini-batch termination bound of Schwartzman (arXiv:2304.00419): O(1)
iterations per round suffice for normalized kernels at b = Theta(log n).
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

import numpy as np

from repro.service.buffer import IngestBuffer
from repro.service.faults import fire
from repro.service.snapshot import SnapshotStore


class Learner:
    """Drives one estimator's fit stream from an ingest buffer.

    Parameters
    ----------
    estimator : a ``KernelKMeans`` on a ``partial_fit``-capable plan
        (``restarts=1, distribution='single', cache='none'``).
    buffer : the bounded ingest buffer (content pure in ``(seed, step)``).
    source : ``source(step) -> (m, d)`` deterministic arrival stream —
        in production the drained ingest queue keyed by sequence number,
        in tests/demos a synthetic generator.
    store : snapshot store shared with the actors.
    iters_per_round : mini-batch iterations per round (default: the
        config's ``max_iters``, which also governs the cold-start ``fit``
        of round 0; the config's ``epsilon`` early-stops within a round).
    publish_every : publish a snapshot every this many rounds.
    warmup_pushes : buffer pushes before round 0 (default: enough to
        fill — the learner never fits a part-empty buffer).
    seed : fit key for the initial ``fit`` (rounds resume its stream).
    """

    def __init__(self, estimator, buffer: IngestBuffer,
                 source: Callable[[int], np.ndarray], store: SnapshotStore,
                 *, iters_per_round: Optional[int] = None,
                 publish_every: int = 5,
                 warmup_pushes: Optional[int] = None, seed: int = 0,
                 on_round: Optional[Callable[[int], None]] = None,
                 log_every: int = 0, faults=None,
                 step_timeout_s: Optional[float] = None,
                 backoff_base_s: float = 0.0):
        self.est = estimator
        self.buffer = buffer
        self.source = source
        self.store = store
        self.iters_per_round = int(iters_per_round
                                   if iters_per_round is not None
                                   else estimator.config.max_iters)
        self.publish_every = int(publish_every)
        self.seed = seed
        self.on_round = on_round
        self.log_every = int(log_every)
        self.faults = faults
        self.step_timeout_s = step_timeout_s
        self.backoff_base_s = float(backoff_base_s)
        if warmup_pushes is None:
            warmup_pushes = (buffer.capacity if buffer.mode == "reservoir"
                             else 1)
        self.warmup_pushes = int(warmup_pushes)
        self.rounds = 0
        self.restores = 0
        self.last_improvement = None
        # degraded-mode counters: run_resilient fills events, the carry
        # guard fills guard_* — all surfaced via stats()/telemetry.poll()
        self.events: dict = {}
        self.guard_patched = 0
        self.guard_reseeded = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------- plumbing
    def _round_buffer(self, rnd: int) -> np.ndarray:
        """Buffer snapshot for round ``rnd`` — pure in (seed, rnd); replays
        the stream when recovery rewound (or skipped ahead of) the
        cursor."""
        return self.buffer.replay_to(self.source,
                                     self.warmup_pushes + rnd + 1)

    def _step(self, carry, xbuf: np.ndarray):
        """One learner round under the ``run_resilient`` protocol:
        ``(carry, batch) -> (carry, metrics)``.  ``carry=None`` means
        cold start (initial ``fit`` draws init + key stream from
        ``seed``); afterwards the carry is always HOST-materialized, so
        the donating resume program can never invalidate it."""
        fire(self.faults, "learner.step")
        if carry is None:
            self.est.fit(xbuf, key=self.seed)
        else:
            self.est.restore_carry(carry)
            self.est.partial_fit(xbuf, iters=self.iters_per_round)
        self._guard(xbuf)
        if self.est.config.compress != "off":
            # round-cadence landmark compression: every published snapshot
            # carries the O(k*m) serving representation (stable serving
            # shapes across swaps -> zero actor recompiles), while the
            # resumable carry stays the full window.  Selection is keyed
            # by the carried step counter, so a crash-recovered learner
            # republishes bit-identical compressed models.
            self.est.compress()
        if self.on_round is not None:
            self.on_round(self.rounds)
        self.rounds += 1
        hist = self.est.history_
        if hist:
            self.last_improvement = hist[-1]["improvement"]
        if self.log_every and self.rounds % self.log_every == 0:
            from repro.service import telemetry
            print(telemetry.format_line(telemetry.poll(learner=self)),
                  flush=True)
        return self.est.snapshot_carry(), {"iters": int(self.est.iters_)}

    def _guard(self, xbuf: np.ndarray) -> None:
        """Non-finite-carry guard + dead-center reseed through the loop
        core (:func:`repro.core.loop.guard_carry`): degenerate arrivals
        (all-NaN rows, empty clusters — Tang & Monteleoni's stochastic
        k-means instability) can zero or poison center coefficients; the
        guard repairs the carry BEFORE it is compressed, published, or
        resumed.  Clean carries pass through untouched (same object), so
        the healthy path stays bit-identical."""
        from repro.core.loop import guard_carry

        host = self.est.snapshot_carry()
        if host is None:
            return
        kernel = (self.est.plan_.executor.kernel
                  if self.est.plan_ is not None else None)
        guarded, rep = guard_carry(host, x=xbuf, kernel=kernel,
                                   seed=self.seed, faults=self.faults)
        if rep.clean:
            return
        self.guard_patched += rep.patched
        self.guard_reseeded += rep.reseeded
        self.est.restore_carry(guarded)

    # --------------------------------------------------------------- run
    def run(self, n_rounds: int, max_restarts: int = 3,
            publish_final: bool = True):
        """Run ``n_rounds`` with crash recovery (``run_resilient`` over
        the snapshot-store checkpointer).  Returns the final host carry."""
        from repro.train.resilience import run_resilient

        ckpt = self.store.as_checkpointer(self.est)

        def on_restore(version: int) -> None:
            self.restores += 1
            self.rounds = version

        on_watchdog = (self.faults.abort_hangs
                       if self.faults is not None else None)
        carry, _ = run_resilient(
            self._step, self._round_buffer, None, n_rounds, ckpt,
            ckpt_every=self.publish_every, max_restarts=max_restarts,
            on_restore=on_restore, step_timeout_s=self.step_timeout_s,
            backoff_base_s=self.backoff_base_s,
            backoff_seed=int(self.seed) if np.isscalar(self.seed) else 0,
            on_watchdog=on_watchdog, events=self.events)
        if publish_final and self.rounds % self.publish_every != 0:
            self.store.publish(self.est, self.rounds)
        return carry

    # ------------------------------------------------- background thread
    def start(self, n_rounds: int, **kw) -> threading.Thread:
        """Run in a daemon thread (the ``--service`` demo wiring); the
        thread exits after ``n_rounds`` or on :meth:`stop`."""

        def _loop():
            try:
                self.run(n_rounds, **kw)
            except _Stopped:
                pass

        prev = self.on_round

        def _guard(rnd):
            if self._stop.is_set():
                raise _Stopped
            if prev is not None:
                prev(rnd)

        self.on_round = _guard
        self._thread = threading.Thread(target=_loop, daemon=True,
                                        name="service-learner")
        self._thread.start()
        return self._thread

    def stop(self, timeout: Optional[float] = 10.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def stats(self) -> dict:
        return dict(rounds=self.rounds, publishes=self.store.publishes,
                    restores=self.restores,
                    last_improvement=self.last_improvement,
                    watchdog_fires=int(self.events.get(
                        "watchdog_fires", 0)),
                    restore_fallbacks=int(self.events.get(
                        "restore_fallbacks", 0)
                        + self.store.load_fallbacks),
                    guard_patched=self.guard_patched,
                    guard_reseeded=self.guard_reseeded)


class _Stopped(BaseException):
    """Cooperative stop signal.  Derives from BaseException so it passes
    straight through ``run_resilient``'s crash-recovery ``except
    Exception`` instead of triggering a restore."""

