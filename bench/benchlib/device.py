"""The device a run is on, its peaks, its memory, and the compile clock."""
from __future__ import annotations

import os

from benchlib.spec import BENCH_DIR, load_json


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise NoChip(f"needs a TPU, JAX found {info}")
    if info["count"] < chips:
        raise NoChip(f"needs {chips} chips, JAX found {info}")
    return info


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks(kind: str) -> dict:
    """The peak row of ``bench/peaks.json`` for a ``device_kind``.  A
    device that is not in the table is an error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[kind]


# jax.monitoring duration events that mean a program was traced, lowered,
# compiled or read back from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts and sums JAX's compile events (all threads), from
    ``jax.monitoring``: ``events`` counts every trace, lowering and
    compile, ``backend_compiles`` the programs compiled or read back from
    the persistent cache."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1
            if event == BACKEND_COMPILE:
                self.backend_compiles += 1
