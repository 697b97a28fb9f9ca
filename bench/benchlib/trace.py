"""From a profiler trace to device busy time, op times and idle gaps.

A traced run records the measured window with ``jax.profiler``; the
benchmark's own ``TraceAnnotation`` spans (``bench.*``) sit on the host
plane of the same trace.  ``load_events`` reads the ``.xplane.pb`` into
plain tuples; ``reduce`` is pure Python over them, so the tests check it
on a small recorded trace kept in ``bench/testdata``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import json
import os

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Events:
    ops: list       # (name, start_ns, dur_ns, device_plane) of device ops
    spans: list     # (name, start_ns, dur_ns) of the bench.* host spans

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(ops=[tuple(o) for o in d["ops"]],
                   spans=[tuple(s) for s in d["spans"]])


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # union of device op intervals / chips
    op_s: dict                    # op name -> device seconds (all chips)
    idle_gaps: list               # [(host span, seconds)] summed per span
    spans: dict                   # span name -> (count, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self, top: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]


@contextlib.contextmanager
def capture(directory: str):
    """Trace everything inside the block into ``directory``."""
    import jax

    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def load_events(directory: str) -> Events:
    """Device ops (the "XLA Ops" line of each device plane) and the
    bench.* host spans of the newest trace under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    ops, spans = [], []
    for plane in data.planes:
        if _device_plane(plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append((e.name, float(e.start_ns),
                                float(e.duration_ns), plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return Events(ops=ops, spans=spans)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _attribute(gaps: list, spans: list):
    """Yield (innermost span covering the gap's midpoint, gap length) for
    gaps sorted by midpoint, in one sweep over spans sorted by start."""
    active, i = [], 0
    for mid, length in gaps:
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] + sp[2] >= mid]
        yield (min(active, key=lambda sp: sp[2])[0] if active
               else "host: none"), length


def short_name(op: str) -> str:
    """``%fusion.12`` of an HLO op's text ``%fusion.12 = f32[...] ...``."""
    return op.split(" = ", 1)[0]


def _leaves(ops: list) -> list:
    """The ops that hold no other op: the device trace nests a loop's body
    ops inside the loop op on the same line, and only the innermost ops
    are counted by name (the union of intervals needs no such care)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= o[1] + o[2]:
            out.append(o)
    return out


def reduce(ev: Events) -> Summary:
    """Busy time, op times and idle gaps inside the ``bench.window`` span.

    Busy is the union of device op intervals, per device plane, averaged
    over the planes.  Op times sum the innermost ops by short name.  An
    idle gap is a stretch of the window in which no op ran on a plane; its
    time goes to the innermost bench.* span that covers the gap's
    midpoint (``host: none`` where no span does)."""
    win = [s for s in ev.spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    _, w0, wd = max(win, key=lambda s: s[2])
    w1 = w0 + wd
    planes = collections.defaultdict(list)
    for name, s, d, plane in ev.ops:
        cs, ce = _clip(s, s + d, w0, w1)
        if ce > cs:
            planes[plane].append((name, cs, ce - cs))
    op_s = collections.Counter()
    for plane, ops in planes.items():
        for name, s, d in _leaves(ops):
            op_s[short_name(name)] += d * 1e-9
        planes[plane] = [(s, s + d) for _, s, d in ops]
    inner = sorted((s for s in ev.spans if s[0] != WINDOW_SPAN),
                   key=lambda sp: sp[1])
    gaps = collections.Counter()
    busy = []
    for iv in planes.values():
        u = _union(iv)
        busy.append(sum(e - s for s, e in u))
        edges = [w0] + [x for se in u for x in se] + [w1]
        found = [(0.5 * (gs + ge), ge - gs)
                 for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
        for who, sec in _attribute(sorted(found), inner):
            gaps[who] += sec * 1e-9 / len(planes)
    if not planes:
        gaps["host: none"] = wd * 1e-9
    spans = collections.defaultdict(lambda: [0, 0.0])
    for name, s, d in inner:
        cs, ce = _clip(s, s + d, w0, w1)
        if ce > cs:
            spans[name][0] += 1
            spans[name][1] += (ce - cs) * 1e-9
    n = max(len(planes), 1)
    return Summary(window_s=wd * 1e-9,
                   busy_s=sum(busy) * 1e-9 / n,
                   op_s=dict(op_s),
                   idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
                   spans={k: tuple(v) for k, v in spans.items()})


def read_events(path: str) -> Events:
    with gzip.open(path, "rt") as f:
        return Events.from_json(json.load(f))
