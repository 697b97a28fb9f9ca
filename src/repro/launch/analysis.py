"""Compiled-program analysis (HLO memory / cost / collective bytes).

Used in-process by ``KernelKMeans.explain(deep=True)`` and
``serve --dry-run`` to analyze a compiled step program.
"""
from __future__ import annotations

import re

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
SHAPE_RE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes_of(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the compiled HLO (the
    spec's §Roofline recipe).  Falls back to the result shape when operand
    shapes are not printed on the line."""
    totals = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        op = m.group(1)
        # shapes on the line: first = result, rest = operands
        shapes = SHAPE_RE.findall(line)
        if not shapes:
            continue
        operands = shapes[1:] if len(shapes) > 1 else shapes[:1]
        nbytes = 0
        for dt, dims in operands:
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        totals[op] = totals.get(op, 0) + nbytes
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


def analyze_compiled(compiled) -> dict:
    """Memory / cost / collective summary of one ``jax`` Compiled object."""
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0] if cost else {}
    coll = collective_bytes_of(compiled.as_text())
    return {
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "cost": {"flops_per_device": float(cost.get("flops", 0.0)),
                 "bytes_per_device": float(cost.get("bytes accessed", 0.0))},
        "collectives": coll,
    }
