"""The program's stages and host spans in a profiler trace.

The fit path names its device stages with ``jax.named_scope`` and its host
work with ``TraceAnnotation`` spans, all ``kkm.*`` (``repro.core.loop
.STAGES``).  A stage lives in each HLO instruction's metadata ``op_name``,
the scope path ``jit(run)/kkm.loop/while/body/kkm.assign/...``, which
``ProfileData`` does not show.  The trace does carry each program's
optimized HLO module in its ``/host:metadata`` plane, so ``load_events``
joins every device op to its instruction there: by the program running
on that device at the op's start (the "XLA Modules" line) and the
instruction's name.  A fusion carries its root instruction's op name;
where XLA built the root without one, the op name nearest the root among
the fused instructions.  An op's stage is the innermost ``kkm.*``
component of its path, ``unscoped`` where there is none.

``reduce`` adds to ``trace.reduce`` (whose numbers it leaves as they are)
the device seconds per stage and each span's count, total and self time;
idle gaps go to the innermost ``bench.*`` or ``kkm.*`` span over their
midpoint.  The module reads the protobuf wire format itself, so it needs
nothing beyond JAX.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os

from benchlib import trace

STAGE_PREFIX = "kkm."
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"


# ------------------------------------------------- protobuf wire format
def _varint(buf, i: int):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _first(buf, num: int, default=None):
    for k, v in _fields(buf):
        if k == num:
            return v
    return default


def hlo_modules(raw: bytes) -> dict:
    """Program name (as the "XLA Modules" line names it, ``jit_run(id)``)
    -> serialized ``HloModuleProto``, from the trace's metadata plane.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
    value = 2); XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6;
    HloProto.hlo_module = 1."""
    out = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1 or bytes(_first(plane, 2, b"")) != \
                METADATA_PLANE.encode():
            continue
        for k, entry in _fields(plane):
            if k != 4:
                continue
            md = _first(entry, 2, b"")
            name = bytes(_first(md, 2, b"")).decode()
            for s, stat in _fields(md):
                proto = _first(stat, 6) if s == 5 else None
                if proto is not None:
                    out[name] = bytes(_first(proto, 1, b""))
    return out


def op_names(module: bytes) -> dict:
    """Instruction name -> metadata op name over every computation of an
    ``HloModuleProto`` (computations = 3; HloComputationProto.instructions
    = 2, .id = 5; HloInstructionProto.name = 1, .opcode = 2, .metadata = 7,
    .called_computation_ids = 38; OpMetadata.op_name = 2)."""
    comps, fusions, names = {}, [], {}
    for num, comp in _fields(memoryview(module)):
        if num != 3:
            continue
        cid, seen = None, []
        for k, v in _fields(comp):
            if k == 5:
                cid = v
            elif k == 2:
                name = opcode = op = ""
                called = []
                for f, w in _fields(v):
                    if f == 1:
                        name = bytes(w).decode()
                    elif f == 2:
                        opcode = bytes(w).decode()
                    elif f == 7:
                        op = bytes(_first(w, 2, b"")).decode()
                    elif f == 38:
                        if isinstance(w, int):
                            called.append(w)
                        else:
                            j = 0
                            while j < len(w):
                                c, j = _varint(w, j)
                                called.append(c)
                names[name] = op
                seen.append(op)
                if opcode == "fusion" and not op and called:
                    fusions.append((name, called[0]))
        comps[cid] = seen
    for name, cid in fusions:
        # instructions come in post order, the root last
        inner = [op for op in comps.get(cid, ()) if op]
        names[name] = inner[-1] if inner else ""
    return names


def stage_of(path: str) -> str:
    """Innermost ``kkm.*`` component of a scope path."""
    for part in reversed(path.split("/")):
        if part.startswith(STAGE_PREFIX):
            return part.split(":", 1)[0]
    return UNSCOPED


# ------------------------------------------------------- trace -> events
def _newest(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load_events(directory: str) -> trace.Events:
    """``trace.load_events`` with each device op's scope path as a fifth
    element and the ``kkm.*`` host spans beside the ``bench.*`` ones."""
    from jax.profiler import ProfileData

    path = _newest(directory)
    with open(path, "rb") as f:
        raw = f.read()
    modules = hlo_modules(raw)
    resolved = {}
    data = ProfileData.from_serialized_xspace(raw)
    ops, spans = [], []
    for plane in data.planes:
        if trace._device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            progs = sorted((float(e.start_ns), float(e.end_ns), e.name)
                           for e in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            if "XLA Ops" not in lines:
                continue
            events = sorted(((float(e.start_ns), float(e.duration_ns),
                              e.name) for e in lines["XLA Ops"].events))
            j = 0
            for s, d, name in events:
                while j + 1 < len(progs) and progs[j + 1][0] <= s:
                    j += 1
                prog = progs[j][2] if progs and progs[j][0] <= s <= \
                    progs[j][1] else None
                if prog not in resolved:
                    resolved[prog] = (op_names(modules[prog])
                                      if prog in modules else {})
                scope = resolved[prog].get(
                    trace.short_name(name).lstrip("%"), "")
                ops.append((name, s, d, plane.name, scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((trace.SPAN_PREFIX,
                                          STAGE_PREFIX)):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return trace.Events(ops=ops, spans=spans)


def save_events(ev: trace.Events, path: str) -> None:
    """Gzipped JSON that ``trace.read_events`` reads back."""
    with gzip.open(path, "wt") as f:
        json.dump({"ops": [list(o) for o in ev.ops],
                   "spans": [list(s) for s in ev.spans]}, f)


# --------------------------------------------------------------- reduce
@dataclasses.dataclass
class StageSummary:
    base: trace.Summary     # busy, op times, idle gaps (kkm.* spans too)
    stage_s: dict           # stage -> device seconds of its leaf ops
    spans: dict             # span name -> (count, seconds, self seconds)

    def per_step_ms(self, steps: int) -> dict:
        return {k: 1e3 * v / steps for k, v in
                sorted(self.stage_s.items(), key=lambda kv: -kv[1])}

    @property
    def unscoped_share(self) -> float:
        total = sum(self.stage_s.values())
        return self.stage_s.get(UNSCOPED, 0.0) / total if total else 0.0


def _self_times(spans: list, w0: float, w1: float) -> dict:
    """Span name -> (count, seconds, self seconds) inside [w0, w1]: self
    time is the span's time less what its direct children cover."""
    out = collections.defaultdict(lambda: [0, 0.0, 0.0])
    stack = []
    for name, s, d in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        cs, ce = trace._clip(s, s + d, w0, w1)
        while stack and stack[-1][1] < s + d:
            stack.pop()
        inside = max(ce - cs, 0.0) * 1e-9
        if stack:
            out[stack[-1][0]][2] -= inside
        stack.append((name, s + d))
        if inside > 0:
            out[name][0] += 1
            out[name][1] += inside
            out[name][2] += inside
    return {k: tuple(v) for k, v in out.items() if v[0]}


def reduce(ev: trace.Events) -> StageSummary:
    """``trace.reduce`` over the same ops, plus stage seconds (innermost
    ops only, inside ``bench.window``, summed over device planes) and each
    span's self time."""
    base = trace.reduce(trace.Events(ops=[tuple(o[:4]) for o in ev.ops],
                                     spans=ev.spans))
    win = max((s for s in ev.spans if s[0] == trace.WINDOW_SPAN),
              key=lambda s: s[2])
    w0, w1 = win[1], win[1] + win[2]
    planes = collections.defaultdict(list)
    for o in ev.ops:
        cs, ce = trace._clip(o[1], o[1] + o[2], w0, w1)
        if ce > cs:
            planes[o[3]].append((o[4] if len(o) > 4 else "", cs, ce - cs))
    stage_s = collections.Counter()
    for ops in planes.values():
        for scope, _, d in trace._leaves(ops):
            stage_s[stage_of(scope)] += d * 1e-9
    inner = [s for s in ev.spans if s[0] != trace.WINDOW_SPAN]
    return StageSummary(base=base, stage_s=dict(stage_s),
                        spans=_self_times(inner, w0, w1))
