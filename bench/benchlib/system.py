"""The system under test, built from a configuration file."""
from __future__ import annotations

import jax

from benchlib import data

# key streams of one seed: each use draws independently
MIXTURE, WARM, FIT, DATASET, SAMPLE = range(1, 6)


def solver_config(config: dict, **override):
    """The configuration's ``SolverConfig``."""
    from repro.api import SolverConfig

    fields = {f: config[f] for f in (
        "k", "batch_size", "tau", "rate", "sqnorm_mode", "eval_mode",
        "epsilon", "max_iters", "kernel", "cache", "distribution",
        "precision", "step")}
    fields["kernel_params"] = {"kappa": float(config["kappa"])}
    fields.update(override)
    return SolverConfig(**fields)


def dataset(config: dict, seed: int):
    """(mixture centers, x (n, d)) on the device, from the seed."""
    centers = data.make_mixture(config, seed)
    x, _ = data.points(config, centers, seed, DATASET, config["n"])
    x.block_until_ready()
    return centers, x


def fitted(config: dict, x, seed: int, **override):
    """An estimator fitted once on x from the seed (also the warm-up of
    every fit program)."""
    from repro.api import KernelKMeans

    est = KernelKMeans(solver_config(config, **override))
    with jax.profiler.TraceAnnotation("bench.warmup"):
        est.fit(x, data.seed_key(seed, WARM))
        jax.block_until_ready(est.state_)
    return est


def sample_rng(seed: int):
    """numpy Generator for the seed's draws of which answers to check."""
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, SAMPLE])
