#!/usr/bin/env python3
"""Per-stage device time, host spans and idle time of one cell's window.

    python3 bench/stage_times.py --workload fit.mnist_rbf --seed 7 \
        --seconds 10

Sets the cell up as ``bench/run.py`` does, traces the measured window and
prints one JSON line: the window's counters, device busy and idle time,
the device milliseconds per fit iteration of each ``kkm.*`` stage of the
program (``benchlib.scopes``, ``unscoped`` for ops outside every stage),
each host span's count, total and self seconds, and the idle seconds
under each span.  It checks no answers and reports no benchmark metric:
``bench/run.py`` does both.  ``--save PATH`` also writes the window's
events as gzipped JSON, which ``benchlib.trace.read_events`` reads back.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402  (the src path, the compile cache, the log)


def main(argv=None, *, require_tpu: bool = True, config_override=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", help="write the window's events here")
    args = ap.parse_args(argv)
    from benchlib import device, scopes, spec, trace

    cell = spec.load_cell(args.workload)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if require_tpu:
        run.enable_compile_cache()
        try:
            info = device.require_chips(cell.chips)
        except device.NoChip as e:
            print(f"stage_times: {args.workload}: {e}", file=sys.stderr)
            return 2
    else:
        info = device.device_info()
    import jax

    drv = spec.driver(cell.traffic["driver"]).Run(
        cell, seed=args.seed, control=False, log=run.log)
    drv.setup()
    tdir = tempfile.mkdtemp(prefix="bench_stages_")
    try:
        with trace.capture(tdir):
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                win = drv.window(args.seconds)
        ev = scopes.load_events(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if args.save:
        scopes.save_events(ev, args.save)
    s = scopes.reduce(ev)
    steps = win.counters.get("steps") or 1
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "device": info,
        "counters": {k: win.counters[k] for k in ("window_s", "fits",
                                                  "steps")
                     if k in win.counters},
        "window_s": s.base.window_s, "busy_s": s.base.busy_s,
        "idle_share": s.base.idle_share,
        "stage_ms_per_step": s.per_step_ms(steps),
        "unscoped_share": s.unscoped_share,
        "spans": s.spans,
        "idle_gaps": s.base.idle_gaps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
