"""Back-to-back whole fits through ``KernelKMeans.fit``.

Set-up draws the configuration's dataset on the device from the seed,
builds the estimator and warms it with one whole fit (the k-means++ init
and the early-stopped fit loop, compiled or read from the cache).  The
window then runs whole fits, each from a fresh key derived from the seed,
and ends with the first fit that finishes after ``--seconds``;
``fit_points_per_s`` is the batch points of every completed iteration
over all of that time.

The check compares a sample of the window's fitted states, drawn from the
seed, with the reference (``benchlib.checks.fit_numbers``).  With
``--control`` the reference at its lower precision stands in the program's
place for what the check compares.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchlib import checks, data, spec, system
from benchlib.window import Window

STATE_FIELDS = ("idx", "coef", "head", "sqnorm", "counts")


class Run:
    def __init__(self, cell, *, seed: int, control: bool, log):
        self.cell, self.cfg, self.seed = cell, cell.config, seed
        self.control, self.log = control, log
        self.fits = []

    def setup(self):
        _, self.x = system.dataset(self.cfg, self.seed)
        self.est = system.fitted(self.cfg, self.x, self.seed)
        self.log(f"plan={self.est.plan_.name} "
                 f"step={self.est.plan_.executor.mb.step}")

    def _fit(self, key):
        self.est.fit(self.x, key)
        jax.block_until_ready(self.est.state_)
        return self.est.state_, int(self.est.iters_)

    def window(self, seconds: float) -> Window:
        base = data.seed_key(self.seed, system.FIT)
        t0 = time.perf_counter()
        while True:
            key = jax.random.fold_in(base, len(self.fits))
            with jax.profiler.TraceAnnotation("bench.fit"):
                self.fits.append((*self._fit(key), key))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        iters = [it for _, it, _ in self.fits]
        b = int(self.cfg["batch_size"])
        # after the window: the points each center took in each fit, for
        # the active rows that the roofline share counts
        center_counts = [np.asarray(st.counts).tolist()
                         for st, _, _ in self.fits]
        return Window(
            end_to_end={"fit_points_per_s": sum(iters) * b / elapsed},
            attempted=len(self.fits), failed=0,
            counters={"window_s": elapsed, "fits": len(self.fits),
                      "steps": sum(iters), "iters": iters,
                      "center_counts": center_counts})

    def release(self):
        """Pull the sampled states to the host and free the program."""
        n = min(int(self.cell.traffic["check_fits"]), len(self.fits))
        pick = sorted(system.sample_rng(self.seed).choice(
            len(self.fits), n, replace=False))
        self.sample = [({f: np.asarray(getattr(st, f)) for f in STATE_FIELDS},
                        it, key) for st, it, key in (self.fits[i] for i in pick)]
        self.fits.clear()
        del self.est

    def check(self) -> dict:
        ref = spec.reference(self.cfg["reference"])
        return checks.worst([checks.fit_numbers(st, it, key, self.x,
                                                self.cfg, ref, self.control,
                                                log=self.log)
                             for st, it, key in self.sample])
