"""Back-to-back whole fits on a k-nn graph Gram through ``KernelKMeans.fit``.

Set-up draws the configuration's points on the device from the seed
(``benchlib.data``), builds the k-nn Gram G = D^-1 A D^-1 on the device
through ``repro.data.graph_kernels.knn_kernel`` (the paper's
preprocessing: the Gram is built once and every fit reads it), builds the
estimator on that ``Precomputed`` kernel and its (n, 1) index data, and
warms it with one whole fit.  The window is the ``fit`` driver's
(``bench/drivers/fit.py``) on the index data; its counters add each
fit's active support slots per center and the recompute's route, which
the Gram-row kernel's roofline reads.

The check compares a sample of the window's fitted states, drawn from the
seed, with the plain reference on its own Gram, built on the host from
the same points (``bench/references/knn_graph.py``), through
``benchlib.checks.fit_numbers``, and adds ``gram_err``: the program's
Gram against the reference's.  With ``--control`` the reference at the
configuration's ``control`` precision (fp8 unless it names ``bfloat16``)
stands in the program's place for what the check compares.
"""
from __future__ import annotations

import jax
import numpy as np

from benchlib import checks, data, spec
from benchlib.system import WARM, dataset

SOLVER_FIELDS = ("k", "batch_size", "tau", "rate", "sqnorm_mode",
                 "eval_mode", "epsilon", "max_iters", "cache",
                 "distribution", "precision", "step")


class Run(spec.driver("fit").Run):
    def setup(self):
        from repro.api import KernelKMeans, SolverConfig
        from repro.core.minibatch import sqnorm_route
        from repro.data.graph_kernels import knn_kernel

        c = self.cfg
        _, self.points = dataset(c, self.seed)
        self.kernel, self.x = knn_kernel(self.points, k=int(c["neighbors"]))
        jax.block_until_ready(self.kernel.gram)
        self.route = sqnorm_route(int(c["k"]), int(c["batch_size"])
                                  + int(c["tau"]), int(c["n"]))
        self.est = KernelKMeans(SolverConfig(
            kernel=self.kernel, **{f: c[f] for f in SOLVER_FIELDS}))
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self.est.fit(self.x, data.seed_key(self.seed, WARM))
            jax.block_until_ready(self.est.state_)
        self.log(f"plan={self.est.plan_.name} "
                 f"step={self.est.plan_.executor.mb.step} "
                 f"sqnorm_route={self.route}")

    def window(self, seconds: float):
        win = super().window(seconds)
        win.counters["active_slots"] = [
            (np.asarray(st.coef) != 0).sum(axis=1).tolist()
            for st, _, _ in self.fits]
        win.counters["sqnorm_route"] = self.route
        return win

    def release(self):
        """Pull the sampled states and the Gram to the host and free the
        program."""
        super().release()
        self.gram = np.asarray(self.kernel.gram)
        del self.kernel

    def check(self) -> dict:
        ref = spec.reference(self.cfg["reference"])
        graph = ref.build(np.asarray(self.points), int(self.cfg["neighbors"]))
        bound = ref.Bound(graph.gram, self.cfg.get("control", "float8"))
        self.log(f"tie_rows={int(graph.ties.sum())} "
                 f"left_out_of_gram_err={int(graph.affected.sum())}")
        # fit_numbers hands kappa to the reference, which has none to use
        cfg = {**self.cfg, "kappa": 0.0}
        out = checks.worst([checks.fit_numbers(st, it, key, self.x, cfg,
                                               bound, self.control,
                                               log=self.log)
                            for st, it, key in self.sample])
        got = (bound.operand(graph.gram, "control") if self.control
               else self.gram)
        out["gram_err"] = ref.gram_err(got, graph)
        return out
