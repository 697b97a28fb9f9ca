"""Deterministic fault injection for the always-on service.

The service's robustness claims — crash recovery bit-identical to an
uninterrupted run, no torn or corrupt snapshot ever served, bounded
degradation under transient I/O errors — are only worth something if the
faults that threaten them can be REPLAYED.  This module is that replay
harness: a :class:`FaultPlan` is a schedule of named faults over the
service's injection sites, and every firing decision is a pure function
of ``(plan seed, site, occurrence index)`` — never of wall clock, thread
interleaving, or prior RNG state.  Running the same plan against the
same deterministic workload twice produces the same fault trace twice
(``tests/test_faults.py`` asserts exactly this).

Sites (each component fires its site at one well-defined point; with
``faults=None`` — the default everywhere — the injection points are
dead branches and every path is bit-identical to the un-instrumented
code):

==================  ======================================================
``learner.step``    entry of one learner round (:meth:`Learner._step`)
``snapshot.publish``inside :meth:`SnapshotStore.publish`'s retry loop
``snapshot.load``   entry of :meth:`SnapshotStore.load` / ``load_version``
``actor.swap``      entry of :meth:`Actor.try_swap`
``actor.serve``     inside :meth:`Actor._serve`'s retry loop
``buffer.push``     :meth:`IngestBuffer.push`, keyed by the PUSH INDEX so
                    crash-recovery replay re-fires identically
``loop.carry``      the loop core's carry guard
                    (:func:`repro.core.loop.guard_carry`)
==================  ======================================================

Kinds:

* ``crash`` — raise :class:`InjectedFault` (recovery path: restore +
  replay).
* ``hang`` — block for ``delay_s`` (default 60s) and then raise: a hung
  step never silently resumes into restored state.  The watchdog in
  :func:`repro.train.resilience.run_resilient` aborts the wait early via
  :meth:`FaultPlan.abort_hangs`.
* ``slow`` — sleep ``delay_s`` (default 50ms) and continue; exercises
  latency bounds, not recovery.
* ``io`` — raise a transient ``OSError`` (retry/backoff paths).
* ``corrupt`` — returned to the site as a data event; the site flips
  bytes via :meth:`FaultPlan.corrupt_file` (snapshot integrity +
  quarantine paths).
* ``nan`` — returned to the site as a data event; the site poisons rows
  via :meth:`FaultPlan.nan_rows` (the non-finite guard + dead-center
  reseed paths, Tang & Monteleoni's degenerate-batch instability).

See docs/robustness.md for the recovery guarantee each site+kind pair
exercises; ``tests/test_faults.py`` runs the faults one at a time and
together, under an actor soak.
"""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

SITES = ("learner.step", "snapshot.publish", "snapshot.load",
         "actor.swap", "actor.serve", "buffer.push", "loop.carry")
KINDS = ("crash", "hang", "slow", "io", "corrupt", "nan")

# the kinds fire() resolves itself (control flow); 'corrupt'/'nan' are
# data kinds the SITE applies to its payload via the helpers below
_CONTROL_KINDS = ("crash", "hang", "slow", "io")

_DEFAULT_DELAYS = {"hang": 60.0, "slow": 0.05}


class InjectedFault(RuntimeError):
    """A fault fired by a :class:`FaultPlan` (crash / aborted hang)."""


class FaultRule(NamedTuple):
    """One scheduled fault.  Exactly one trigger should be given:

    ``at``     — fire at these occurrence indices of ``site`` (0-based).
    ``every``  — fire at every ``every``-th occurrence (occ > 0).
    ``prob``   — fire when the seeded draw for (seed, site, rule, occ)
                 falls below ``prob`` — random-looking but replayable.

    ``max_fires`` caps total firings (0 = unlimited); ``delay_s``
    overrides the hang/slow duration."""

    site: str
    kind: str
    at: Tuple[int, ...] = ()
    every: int = 0
    prob: float = 0.0
    max_fires: int = 0
    delay_s: Optional[float] = None


class FaultEvent(NamedTuple):
    """One firing, as recorded in the trace."""

    site: str
    kind: str
    occ: int            # occurrence index of the site at firing time
    rule: int           # index into the plan's rule list


class FaultPlan:
    """A deterministic, replayable fault schedule.

    Thread-safe: sites fire from the learner thread, the actor's worker
    and swapper threads, and test drivers concurrently; occurrence
    counters and the trace are guarded by one lock.  Determinism still
    requires the CALLER's occurrence order to be deterministic — sites
    driven by a deterministic workload (the learner round loop, the
    buffer push index) are; free-running poll loops (``actor.swap``) get
    a deterministic trace only relative to their own poll count.
    """

    def __init__(self, rules: Sequence[FaultRule], seed: int = 0):
        for r in rules:
            if r.site not in SITES:
                raise ValueError(f"unknown site {r.site!r} (not in {SITES})")
            if r.kind not in KINDS:
                raise ValueError(f"unknown kind {r.kind!r} (not in {KINDS})")
        self.rules = tuple(rules)
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._occ = {s: 0 for s in SITES}
        self._fires = [0] * len(self.rules)
        self.trace: List[FaultEvent] = []
        self._abort = threading.Event()

    # ------------------------------------------------------------ firing
    def _matches(self, rule_no: int, rule: FaultRule, occ: int) -> bool:
        if rule.max_fires and self._fires[rule_no] >= rule.max_fires:
            return False
        if rule.at:
            return occ in rule.at
        if rule.every:
            return occ > 0 and occ % rule.every == 0
        if rule.prob > 0.0:
            site_id = SITES.index(rule.site)
            draw = np.random.default_rng(
                (self.seed, site_id, rule_no, occ)).random()
            return bool(draw < rule.prob)
        return False

    def fire(self, site: str, index: Optional[int] = None):
        """Advance ``site``'s occurrence counter (or use the caller's
        ``index`` — the buffer keys by push index so replay re-fires
        identically) and evaluate every matching rule.  Control kinds
        execute here (crash/io raise, hang/slow block); data kinds
        (``corrupt`` / ``nan``) are RETURNED for the site to apply.
        Returns the fired data event, or None."""
        with self._lock:
            occ = self._occ[site] if index is None else int(index)
            # caller-indexed sites still advance the high-water mark so
            # occurrences() stays meaningful (replays don't double-count)
            self._occ[site] = max(self._occ[site], occ + 1)
            fired = []
            for rule_no, rule in enumerate(self.rules):
                if rule.site != site or not self._matches(rule_no, rule,
                                                          occ):
                    continue
                self._fires[rule_no] += 1
                ev = FaultEvent(site, rule.kind, occ, rule_no)
                self.trace.append(ev)
                fired.append((rule, ev))
        data_event = None
        for rule, ev in fired:
            if ev.kind == "crash":
                raise InjectedFault(f"injected crash at {site}#{occ}")
            if ev.kind == "io":
                raise OSError(f"injected transient IOError at "
                              f"{site}#{occ}")
            if ev.kind == "slow":
                time.sleep(rule.delay_s if rule.delay_s is not None
                           else _DEFAULT_DELAYS["slow"])
            elif ev.kind == "hang":
                self._hang(rule.delay_s if rule.delay_s is not None
                           else _DEFAULT_DELAYS["hang"], site, occ)
            else:                       # corrupt / nan: the site applies
                data_event = ev
        return data_event

    def _hang(self, delay_s: float, site: str, occ: int) -> None:
        """Block until the watchdog aborts us or ``delay_s`` elapses —
        then RAISE either way: a hung step must never silently resume
        (the driver has long since restored from the last snapshot, and
        a resumed zombie would mutate shared state concurrently)."""
        aborted = self._abort.wait(delay_s)
        if aborted:
            self._abort.clear()
        raise InjectedFault(
            f"injected hang at {site}#{occ} "
            f"({'aborted by watchdog' if aborted else 'expired'})")

    def abort_hangs(self) -> None:
        """Wake every in-flight hang (they raise :class:`InjectedFault`
        on their own threads).  Wired as ``run_resilient``'s
        ``on_watchdog`` hook so an abandoned hung step dies instead of
        lingering."""
        self._abort.set()

    # ------------------------------------------------------- data faults
    def nan_rows(self, arr: np.ndarray, event: FaultEvent,
                 frac: float = 0.25) -> np.ndarray:
        """A copy of ``arr`` with a deterministic ``frac`` of its rows
        set to NaN — the degenerate-arrivals fault."""
        rng = np.random.default_rng((self.seed, SITES.index(event.site),
                                     event.rule, event.occ, 0x7AB))
        out = np.array(arr, copy=True)
        n = out.shape[0]
        rows = rng.choice(n, size=max(1, int(n * frac)), replace=False)
        out[rows] = np.nan
        return out

    def nan_leaf(self, arr: np.ndarray, event: FaultEvent,
                 count: int = 4) -> np.ndarray:
        """A copy of a float array with ``count`` deterministic entries
        poisoned to NaN — the carry-corruption fault."""
        rng = np.random.default_rng((self.seed, SITES.index(event.site),
                                     event.rule, event.occ, 0xCA4))
        out = np.array(arr, copy=True, dtype=np.float32)
        flat = out.reshape(-1)
        pos = rng.choice(flat.size, size=min(count, flat.size),
                         replace=False)
        flat[pos] = np.nan
        return out

    def corrupt_file(self, path: str, event: FaultEvent,
                     n_bytes: int = 8) -> None:
        """Flip ``n_bytes`` deterministic bytes of the file in place —
        the disk-corruption fault (the CRC footer must catch it and the
        store must quarantine + fall back)."""
        rng = np.random.default_rng((self.seed, SITES.index(event.site),
                                     event.rule, event.occ, 0xC0))
        with open(path, "r+b") as f:
            f.seek(0, 2)
            size = f.tell()
            # keep clear of the zip end-of-central-directory record so
            # the file still LOOKS like a snapshot — the integrity check,
            # not the container format, must be what catches it
            hi = max(1, size - 128)
            for off in rng.integers(0, hi, n_bytes):
                f.seek(int(off))
                b = f.read(1)
                f.seek(int(off))
                f.write(bytes([b[0] ^ 0xFF]))

    # --------------------------------------------------------- reporting
    def occurrences(self, site: str) -> int:
        with self._lock:
            return self._occ[site]

    def trace_list(self) -> List[Tuple[str, str, int]]:
        """The (site, kind, occurrence) trace — comparable across runs;
        two runs of the same plan against the same workload must match
        exactly."""
        with self._lock:
            return [(e.site, e.kind, e.occ) for e in self.trace]

    def stats(self) -> dict:
        with self._lock:
            return dict(seed=self.seed, rules=len(self.rules),
                        fired=len(self.trace),
                        by_site={s: sum(1 for e in self.trace
                                        if e.site == s)
                                 for s in SITES if any(e.site == s
                                                       for e in self.trace)})


def fire(faults: Optional[FaultPlan], site: str,
         index: Optional[int] = None):
    """The injection-point helper every site calls: a no-op returning
    None when ``faults`` is None (the default everywhere — the
    production path stays bit-identical to the un-instrumented code)."""
    if faults is None:
        return None
    return faults.fire(site, index)
