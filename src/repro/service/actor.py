"""The actor half of the serving split: microbatched ``predict`` /
``transform`` from the latest published snapshot.

Request path: ``submit()`` places a request (any ``(m, d)`` query block)
on a BOUNDED admission queue — a full queue raises :class:`Backpressure`
immediately (the caller sheds load or retries; the queue never grows
unboundedly) — and returns a future.  The worker thread drains the queue
into microbatches, PADS each microbatch up to the smallest configured
bucket size that fits, runs one compiled assignment on the bucket shape,
and scatters the results back to the per-request futures.

Why buckets: the serving executable is compiled per query shape.  Padding
to a small fixed set of shapes means the warmup pass compiles each bucket
ONCE and steady-state serving recompiles NOTHING — the actor counts its
own trace-time compiles (``serve_compiles``), and together with the PR-5
``program_builds()`` counter this is the "zero recompiles after warmup"
gate of ``tests/test_service.py``.

Snapshot swap: a dedicated swapper thread polls the store; a new version
is loaded and WARMED (one padded predict per bucket) entirely OFF the
serving path, then swapped in by one attribute assignment under a lock —
the serving thread never blocks on a load, in-flight requests finish on
the old model, later ones see the new one, and no request ever observes
a half-loaded estimator.  ``last_swap_pause_ms`` is the measured
load+warm duration (the swap's total cost; the serving-visible pause is
one lock acquisition).  A configurable staleness bound
(``max_staleness_s``) governs ACQUISITION: snapshots older than the bound
are refused (:class:`repro.service.snapshot.StaleSnapshot`), the actor
keeps its current model, and telemetry reports ``stale=True``.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.minibatch import assign_chunked, center_distances_chunked
from repro.service.faults import fire
from repro.service.snapshot import SnapshotStore, StaleSnapshot
from repro.service.telemetry import LatencyWindow

_DEFAULT_BUCKETS = (64, 256, 1024)


class Backpressure(RuntimeError):
    """Admission queue is full — shed load or retry later."""


class _Request:
    __slots__ = ("xq", "kind", "event", "result", "error", "t_submit",
                 "deadline", "cancelled")

    def __init__(self, xq: np.ndarray, kind: str,
                 deadline_s: Optional[float] = None):
        self.xq = xq
        self.kind = kind                  # 'predict' | 'transform'
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_submit = time.perf_counter()
        self.deadline = (time.monotonic() + deadline_s
                         if deadline_s is not None else None)
        self.cancelled = False

    # ------------------------------------------------------ future-ish
    def done(self) -> bool:
        return self.event.is_set()

    def cancel(self) -> None:
        """Mark the request dead to the worker: a cancelled (or
        deadline-expired) request is SKIPPED at serve time instead of
        being padded, computed, and delivered to nobody."""
        self.cancelled = True

    def expired(self) -> bool:
        return (self.cancelled
                or (self.deadline is not None
                    and time.monotonic() > self.deadline))

    def wait(self, timeout: Optional[float] = None):
        if not self.event.wait(timeout):
            self.cancel()       # the worker skips us instead of serving
            raise TimeoutError("request not served within timeout")
        if self.error is not None:
            raise self.error
        return self.result


class Actor:
    """Serves assignment requests from the latest snapshot.

    Parameters
    ----------
    store : snapshot store the learner publishes into.
    buckets : ascending microbatch pad shapes; requests larger than the
        biggest bucket are served in bucket-size slices.
    queue_depth : admission-queue bound (``submit`` raises
        :class:`Backpressure` beyond it).
    max_wait_ms : how long the worker waits to coalesce more requests
        into a non-full microbatch before serving it padded.
    max_staleness_s : refuse to ACQUIRE snapshots older than this
        (``None``: any age).
    poll_every_s : snapshot-version poll period.
    chunk : assignment chunk size (static arg of the compiled program).
    """

    def __init__(self, store: SnapshotStore, *,
                 buckets: Sequence[int] = _DEFAULT_BUCKETS,
                 queue_depth: int = 128, max_wait_ms: float = 2.0,
                 max_staleness_s: Optional[float] = None,
                 poll_every_s: float = 0.25, chunk: int = 4096,
                 faults=None, swap_backoff_cap_s: float = 2.0,
                 serve_retries: int = 1):
        if not buckets or list(buckets) != sorted(set(int(b)
                                                      for b in buckets)):
            raise ValueError("buckets must be ascending unique ints")
        self.store = store
        self.buckets = tuple(int(b) for b in buckets)
        self.max_wait_ms = float(max_wait_ms)
        self.max_staleness_s = max_staleness_s
        self.poll_every_s = float(poll_every_s)
        self.chunk = int(chunk)
        self.faults = faults
        self.swap_backoff_cap_s = float(swap_backoff_cap_s)
        self.serve_retries = int(serve_retries)

        self._queue: "queue.Queue[_Request]" = queue.Queue(
            maxsize=int(queue_depth))
        self._held: Optional[_Request] = None   # mismatched-kind head
        self._model_lock = threading.Lock()
        self._model = None                # (version, serving tuple)
        self._support = None              # support_stats() of served model
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

        # counters / telemetry
        self.latency = LatencyWindow()
        self.submitted = 0
        self.served = 0
        self.rejected = 0
        self.swaps = 0
        self.swap_failures = 0
        self.cancel_skipped = 0
        self.serve_retried = 0
        self.last_swap_pause_ms: Optional[float] = None
        self.stale = False
        self._last_poll = 0.0

        # trace-time compile counters: the wrapped python bodies run only
        # when jax (re)traces — steady state must not increment these
        self._compiles = [0]

        def _assign(kern, coef, sqnorm, sup, xq, chunk):
            self._compiles[0] += 1
            return assign_chunked(kern, coef, sqnorm, sup, xq, chunk)

        def _dists(kern, coef, sqnorm, sup, xq, chunk):
            self._compiles[0] += 1
            return center_distances_chunked(kern, coef, sqnorm, sup, xq,
                                            chunk)

        self._assign = jax.jit(_assign, static_argnames=("chunk",))
        self._dists = jax.jit(_dists, static_argnames=("chunk",))

    # ------------------------------------------------------------ model
    @property
    def serve_compiles(self) -> int:
        """Serving executables traced so far (flat after warmup)."""
        return self._compiles[0]

    @property
    def version(self) -> Optional[int]:
        m = self._model
        return m[0] if m is not None else None

    def _serving_tuple(self, est):
        kern, sup, coef, sqnorm = est._serving_tuple()
        return (kern, jax.numpy.asarray(sup), jax.numpy.asarray(coef),
                jax.numpy.asarray(sqnorm))

    def _warm(self, serving, dim: int) -> None:
        kern, sup, coef, sqnorm = serving
        for b in self.buckets:
            xq = np.zeros((b, dim), np.float32)
            self._assign(kern, coef, sqnorm, sup, xq,
                         self.chunk).block_until_ready()

    def try_swap(self, force: bool = False) -> bool:
        """Poll the store; acquire + warm + atomically swap in a newer
        snapshot.  Returns True when a swap happened.  Respects the
        staleness bound; never touches the served model on failure.

        The load goes through the store's integrity-checked fallback
        path: a corrupt latest snapshot is quarantined and the newest
        INTACT version is acquired instead — a corrupt file can delay a
        swap but can never be swapped in."""
        latest = self.store.latest_version()
        cur = self.version
        if latest is None or (latest == cur and not force):
            if self.max_staleness_s is not None:
                age = self.store.age_s()
                self.stale = age is None or age > self.max_staleness_s
            return False
        t0 = time.perf_counter()
        fire(self.faults, "actor.swap")
        try:
            v, est = self.store.load(max_age_s=self.max_staleness_s)
        except StaleSnapshot:
            self.stale = True
            return False
        except FileNotFoundError:
            return False
        if v == cur and not force:
            return False        # the newest INTACT version is already in
        serving = self._serving_tuple(est)
        self._warm(serving, int(np.asarray(serving[1]).shape[-1]))
        with self._model_lock:
            self._model = (v, serving)
            self._support = est.support_stats()
        self.stale = False
        self.swaps += 1
        self.last_swap_pause_ms = (time.perf_counter() - t0) * 1e3
        return True

    # ---------------------------------------------------------- serving
    def submit(self, xq, kind: str = "predict",
               deadline_s: Optional[float] = None) -> _Request:
        """Enqueue a query block; returns a future-like request.  Raises
        :class:`Backpressure` when the admission queue is full.  With
        ``deadline_s``, the worker skips the request (instead of serving
        it to nobody) once the deadline passes."""
        if kind not in ("predict", "transform"):
            raise ValueError(kind)
        req = _Request(np.asarray(xq, np.float32), kind, deadline_s)
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            self.rejected += 1
            raise Backpressure(
                f"admission queue full ({self._queue.maxsize} deep)") \
                from None
        self.submitted += 1
        return req

    def predict(self, xq, timeout: Optional[float] = 30.0):
        return self.submit(xq, "predict", deadline_s=timeout).wait(timeout)

    def transform(self, xq, timeout: Optional[float] = 30.0):
        return self.submit(xq, "transform",
                           deadline_s=timeout).wait(timeout)

    # ------------------------------------------------------ worker loop
    def start(self) -> "Actor":
        if self._model is None:
            self.try_swap(force=True)
        self._thread = threading.Thread(target=self._serve_loop,
                                        daemon=True, name="service-actor")
        self._swapper = threading.Thread(target=self._swap_loop,
                                         daemon=True,
                                         name="service-actor-swap")
        self._thread.start()
        self._swapper.start()
        return self

    def stop(self, timeout: Optional[float] = 10.0) -> None:
        self._stop.set()
        for t in (self._thread, self._swapper):
            if t is not None:
                t.join(timeout)

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            batch = self._gather()
            if batch:
                self._serve(batch)

    def _swap_backoff_s(self, consec: int) -> float:
        """Poll period, stretched exponentially (with deterministic
        jitter keyed by the failure count) after ``consec`` consecutive
        swap failures — a broken store is polled gently, not hot."""
        if consec <= 0:
            return self.poll_every_s
        jitter = float(np.random.default_rng((0xB0FF, int(consec)))
                       .random())
        return min(self.swap_backoff_cap_s,
                   self.poll_every_s * (2.0 ** consec)) * (1.0
                                                           + 0.25 * jitter)

    def _swap_loop(self) -> None:
        """Load + warm off the serving path; the serving thread only ever
        sees the finished swap (one locked assignment).  Failures are
        COUNTED (``swap_failures``) and back the poll off — the actor
        keeps serving its current model either way."""
        consec = 0
        while not self._stop.wait(self._swap_backoff_s(consec)):
            try:
                self.try_swap()
            except Exception:           # noqa: BLE001 — keep serving
                self.swap_failures += 1
                consec += 1
            else:
                consec = 0

    def _gather(self) -> list:
        """Pop one request (blocking briefly), then coalesce more until
        the biggest bucket fills or ``max_wait_ms`` elapses.  A
        mismatched-kind request is HELD as the next microbatch's head —
        never re-queued to the back (which would reorder admitted
        requests and, on a full queue, error one with Backpressure)."""
        if self._held is not None:
            first, self._held = self._held, None
        else:
            try:
                first = self._queue.get(timeout=self.poll_every_s)
            except queue.Empty:
                return []
        batch, rows = [first], first.xq.shape[0]
        deadline = time.monotonic() + self.max_wait_ms / 1e3
        limit = self.buckets[-1]
        while rows < limit:
            remaining = deadline - time.monotonic()
            # same-kind coalescing keeps the scatter trivial
            try:
                nxt = self._queue.get(timeout=max(remaining, 0) or None) \
                    if remaining > 0 else self._queue.get_nowait()
            except queue.Empty:
                break
            if nxt.kind != first.kind:
                self._held = nxt        # head of the NEXT microbatch
                break
            batch.append(nxt)
            rows += nxt.xq.shape[0]
        return batch

    def _bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]

    def _serve(self, batch: list) -> None:
        # deadline check: a request whose caller already timed out (or
        # cancelled) is skipped, not padded + computed + delivered to
        # nobody
        live = []
        for req in batch:
            if req.expired():
                self.cancel_skipped += 1
                req.error = TimeoutError(
                    "request expired before serving")
                req.event.set()
            else:
                live.append(req)
        batch = live
        if not batch:
            return
        with self._model_lock:
            model = self._model
        if model is None:
            err = RuntimeError("no snapshot available to serve from")
            for req in batch:
                req.error = err
                req.event.set()
            return
        _, (kern, sup, coef, sqnorm) = model
        kind = batch[0].kind
        fn = self._assign if kind == "predict" else self._dists
        flat = None
        for attempt in range(self.serve_retries + 1):
            try:
                fire(self.faults, "actor.serve")
                xq = np.concatenate([r.xq for r in batch], axis=0)
                outs = []
                for lo in range(0, xq.shape[0], self.buckets[-1]):
                    sl = xq[lo:lo + self.buckets[-1]]
                    bucket = self._bucket_for(sl.shape[0])
                    pad = bucket - sl.shape[0]
                    if pad:
                        sl = np.concatenate(
                            [sl,
                             np.broadcast_to(sl[-1:],
                                             (pad,) + sl.shape[1:])])
                    out = fn(kern, coef, sqnorm, sup, sl, self.chunk)
                    outs.append(np.asarray(out)[:bucket - pad])
                flat = np.concatenate(outs, axis=0)
                break
            except Exception as e:        # noqa: BLE001 — retry, then fail
                if attempt >= self.serve_retries:
                    for req in batch:
                        req.error = e
                        req.event.set()
                    return
                self.serve_retried += 1
        t_done = time.perf_counter()
        lo = 0
        for req in batch:
            m = req.xq.shape[0]
            req.result = flat[lo:lo + m]
            lo += m
            self.latency.record((t_done - req.t_submit) * 1e3)
            req.event.set()
            self.served += 1

    # -------------------------------------------------------- telemetry
    def queue_stats(self) -> dict:
        return dict(depth=self._queue.qsize(),
                    capacity=self._queue.maxsize,
                    submitted=self.submitted, served=self.served,
                    rejected=self.rejected,
                    cancel_skipped=self.cancel_skipped,
                    serve_retried=self.serve_retried)

    def snapshot_stats(self) -> dict:
        return dict(version=self.version,
                    age_s=self.store.age_s(self.version),
                    swaps=self.swaps,
                    swap_failures=self.swap_failures,
                    quarantined=self.store.quarantined,
                    last_swap_pause_ms=self.last_swap_pause_ms,
                    stale=self.stale)

    def support_stats(self) -> Optional[dict]:
        """Support-size / compression counters of the SERVED model (the
        last swapped-in snapshot) — ``None`` before the first swap."""
        return self._support
