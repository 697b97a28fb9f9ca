#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python bench/run.py --workload fit.mnist_rbf --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix (``bench/traffic/<mix>.json``); the mix names the driver
(``bench/drivers/<driver>.py``) that sets the system up from ``--seed``,
warms every shape it will use, and drives it for ``--seconds``.  Then the
run compares what the timed path produced with the plain reference and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, last, ``checks`` (each compared number with its limit).
The compared numbers are also the last lines of standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.  ``--control`` runs the lower-precision control in
the program's place (see PERF.md); the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the lower-precision control in the "
                         "program's place")
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself), else
    the checkout's fixed directory.  Every program is cached, however
    quickly it compiled, so only a cell's first run in a checkout
    compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(*parts) -> None:
    print("#", *parts, file=sys.stderr, flush=True)


class Context:
    """What a per-layer metric reader sees: the trace summary (or None),
    the driver's counters, the configuration, the traffic mix, the device
    and its peaks."""

    def __init__(self, summary, counters, config, traffic, device, peaks):
        self.summary = summary
        self.counters = counters
        self.config = config
        self.traffic = traffic
        self.device = device
        self.peaks = peaks


def run(argv=None, *, require_tpu: bool = True, config_override=None,
        t_start: float = T_START):
    """-> (exit code, result dict or None)."""
    args = parse(argv)
    from benchlib import checks, device, spec, trace

    cell = spec.load_cell(args.workload)
    if config_override:
        cell.config = {**cell.config, **config_override}
    cache = enable_compile_cache() if require_tpu else None
    import jax

    if require_tpu:
        try:
            info = device.require_chips(cell.chips)
        except device.NoChip as e:
            print(f"bench: {args.workload}: {e}", file=sys.stderr)
            return 2, None
    else:
        info = device.device_info()
    peaks = device.peaks(info["kind"]) if require_tpu else None
    clock = device.CompileClock()
    drv = spec.driver(cell.traffic["driver"]).Run(
        cell, seed=args.seed, control=args.control, log=log)
    log(f"cell={cell.name} seed={args.seed} device={info} cache={cache}")

    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s!r} compile_s={clock.seconds!r}")

    compiles0, traces0 = clock.backend_compiles, clock.events
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        if tdir:
            with trace.capture(tdir):
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    win = drv.window(args.seconds)
            summary = trace.reduce(trace.load_events(tdir))
        else:
            win = drv.window(args.seconds)
            summary = None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    win.counters["compiles_in_window"] = clock.backend_compiles - compiles0
    win.counters["compile_events_in_window"] = clock.events - traces0
    peak = device.memory_peak_bytes(cell.chips)
    log("window", json.dumps(win.counters, default=float))

    drv.release()
    values = drv.check()
    compared = checks.compare(
        values, cell.config["limits"][cell.traffic["driver"]])
    correct = all(c.ok for c in compared)

    if args.trace:
        ctx = Context(summary, win.counters, cell.config, cell.traffic,
                      info, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values_e2e = {**win.end_to_end, "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values_e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev = {**info, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed), "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops(10)],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in compared}
    for c in compared:
        print(("ok   " if c.ok else "FAIL ") + c.line(), file=sys.stderr)
    return 0, result


def main(argv=None) -> int:
    rc, result = run(argv)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
