"""Uniform service telemetry: one ``poll()`` dict shape + a log line.

Every service component (ingest buffer, learner, actor, Gram tile cache,
compiled-program registry) exports its counters through ONE shape so the
serving demo, the ``service`` benchmark and the existing ``--cache`` serve
path all report the same way:

    {
      "programs": {"fit_builds": int, "serve_compiles": int | None},
      "cache":    {hits, misses, evictions, hit_rate, evals, ...} | None,
      "ingest":   {mode, capacity, pushes, pushed, admitted, dropped, full},
      "queue":    {depth, capacity, submitted, served, rejected,
                   cancel_skipped, serve_retried},
      "snapshot": {version, age_s, swaps, swap_failures, quarantined,
                   last_swap_pause_ms, stale},
      "latency_ms": {p50, p99, count},
      "learner":  {rounds, publishes, restores, watchdog_fires,
                   restore_fallbacks, guard_patched, guard_reseeded,
                   last_improvement},
      "support":  {rows, active, window, k, compressions, m, last_drift,
                   ratio},
    }

The ``support`` section is the serving-cost gauge (docs/compression.md):
``rows`` is the live center-support size W*k the serving path pays per
query — reported whenever a learner or a swapped-in actor model exists,
even with ``compress="off"`` (that is how an operator notices unbounded
growth); the compression counters are populated once the landmark axis is
active.

Sections for components you did not pass are ``None`` — consumers key on
presence, not on argument plumbing.  ``fit_builds`` is always present: it
is the PR-5 cross-executor compile counter
(:func:`repro.api.executors.program_builds`), the "zero recompiles after
warmup" counter that ``tests/test_service.py`` holds flat.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Optional

import numpy as np

_SECTIONS = ("programs", "cache", "ingest", "queue", "snapshot",
             "latency_ms", "learner", "support")


class LatencyWindow:
    """Thread-safe sliding window of latencies (ms) with percentiles."""

    def __init__(self, maxlen: int = 4096):
        self._buf = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.count = 0

    def record(self, ms: float) -> None:
        with self._lock:
            self._buf.append(float(ms))
            self.count += 1

    def percentiles(self, qs=(50, 99)) -> dict:
        with self._lock:
            vals = np.asarray(self._buf, np.float64)
        out = {"count": self.count}
        for q in qs:
            out[f"p{q}"] = (float(np.percentile(vals, q)) if vals.size
                            else None)
        return out


def cache_section(cache) -> Optional[dict]:
    """GramTileCache counters in the uniform shape.  Accepts a
    ``GramTileCache``, a ``CachedKernel`` (unwraps ``.cache``), a stacked
    per-shard cache pytree (counters are summed over the stack), or
    None."""
    if cache is None:
        return None
    from repro.cache.tile_cache import GramTileCache, stats

    inner = getattr(cache, "cache", None)
    if isinstance(inner, GramTileCache):
        cache = inner
    if isinstance(cache, GramTileCache):
        if np.asarray(cache.hits).ndim == 0:
            return stats(cache)
        # stacked per-(restart,)shard caches: sum the counters, report the
        # per-shard geometry of one member
        hits = int(np.sum(np.asarray(cache.hits)))
        misses = int(np.sum(np.asarray(cache.misses)))
        tile = cache.store.shape[-2]
        n = cache.store.shape[-1]
        return dict(hits=hits, misses=misses,
                    evictions=int(np.sum(np.asarray(cache.evictions))),
                    resident=int(np.sum(np.asarray(cache.keys) >= 0)),
                    capacity=int(np.prod(cache.keys.shape)),
                    tile=tile, n_blocks=n // tile,
                    evals=misses * tile * n,
                    hit_rate=hits / max(hits + misses, 1))
    raise TypeError(f"unsupported cache object {type(cache).__name__}")


def poll(*, buffer=None, learner=None, actor=None, cache=None) -> dict:
    """Assemble the uniform telemetry dict from whichever components
    exist.  Always includes ``programs.fit_builds``."""
    from repro.api.executors import program_builds

    out = {name: None for name in _SECTIONS}
    out["programs"] = {
        "fit_builds": program_builds(),
        "serve_compiles": (actor.serve_compiles if actor is not None
                           else None),
    }
    out["cache"] = cache_section(cache)
    if buffer is not None:
        out["ingest"] = buffer.stats()
    if learner is not None:
        out["learner"] = learner.stats()
        if out["ingest"] is None and getattr(learner, "buffer", None) \
                is not None:
            out["ingest"] = learner.buffer.stats()
    if actor is not None:
        out["queue"] = actor.queue_stats()
        out["snapshot"] = actor.snapshot_stats()
        out["latency_ms"] = actor.latency.percentiles()
    # live learner support beats the actor's (possibly stale) snapshot view
    if learner is not None and getattr(learner, "est", None) is not None:
        out["support"] = learner.est.support_stats()
    if out["support"] is None and actor is not None:
        out["support"] = actor.support_stats()
    return out


def _fmt(v, spec=".3g"):
    return "-" if v is None else format(v, spec)


def format_line(t: dict) -> str:
    """One human log line from a ``poll()`` dict — the periodic heartbeat
    the learner/actor threads print."""
    parts = []
    ing = t.get("ingest")
    if ing:
        parts.append(f"ingest push={ing['pushes']} "
                     f"admit={ing['admitted']}/{ing['pushed']} "
                     f"drop={ing['dropped']}")
    lrn = t.get("learner")
    if lrn:
        line = (f"learner rounds={lrn['rounds']} "
                f"pub={lrn['publishes']} restore={lrn['restores']}")
        # degraded-mode counters appear only once nonzero — the healthy
        # heartbeat stays short
        degraded = {"wd": lrn.get("watchdog_fires"),
                    "fb": lrn.get("restore_fallbacks"),
                    "guard": lrn.get("guard_reseeded")}
        extra = " ".join(f"{k}={v}" for k, v in degraded.items() if v)
        parts.append(line + (" " + extra if extra else ""))
    q = t.get("queue")
    if q:
        parts.append(f"queue {q['depth']}/{q['capacity']} "
                     f"served={q['served']} rej={q['rejected']}"
                     + (f" cancel={q['cancel_skipped']}"
                        if q.get("cancel_skipped") else ""))
    snap = t.get("snapshot")
    if snap:
        v = snap["version"]
        parts.append(f"snap v{'-' if v is None else v}"
                     f" age={_fmt(snap['age_s'])}s "
                     f"swaps={snap['swaps']} "
                     f"pause={_fmt(snap['last_swap_pause_ms'])}ms"
                     + (f" fail={snap['swap_failures']}"
                        if snap.get("swap_failures") else "")
                     + (f" quar={snap['quarantined']}"
                        if snap.get("quarantined") else "")
                     + (" STALE" if snap.get("stale") else ""))
    lat = t.get("latency_ms")
    if lat:
        parts.append(f"lat p50={_fmt(lat['p50'])}ms "
                     f"p99={_fmt(lat['p99'])}ms n={lat['count']}")
    sup = t.get("support")
    if sup:
        s = (f"support rows={sup['rows']} active={sup['active']} "
             f"W={sup['window']}")
        if sup.get("compressions"):
            s += (f" m={sup['m']} ratio={_fmt(sup['ratio'])} "
                  f"drift={_fmt(sup['last_drift'])} "
                  f"n={sup['compressions']}")
        parts.append(s)
    cache = t.get("cache")
    if cache:
        parts.append(f"cache hit={cache['hits']} miss={cache['misses']} "
                     f"evict={cache['evictions']} "
                     f"rate={cache['hit_rate']:.2%}")
    prog = t.get("programs") or {}
    parts.append(f"builds fit={prog.get('fit_builds')}"
                 + (f" serve={prog['serve_compiles']}"
                    if prog.get("serve_compiles") is not None else ""))
    return "svc | " + " | ".join(parts)
