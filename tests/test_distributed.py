"""Distributed (shard_map) + multi-restart engine equivalence on 8 virtual
devices — runs in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single real CPU device."""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(script: str, ok_token: str, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert ok_token in r.stdout, r.stdout[-2000:]
    return r.stdout


STEP_EQUIVALENCE = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import MBConfig, Gaussian, init_state, window_size, make_step
    from repro.core.distributed import (
        make_dist_step, init_dist_state, state_shardings, fit_distributed)
    from repro.core.minibatch import sample_batch
    from repro.data import blobs

    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    x, _ = blobs(n=2048, d=16, k=8, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(2.0))
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=8, epsilon=-1.0)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100
    w = window_size(cfg.batch_size, cfg.tau)

    # use_pallas=True additionally exercises the fused Pallas kernel on
    # per-shard support tiles (interpret mode on CPU) inside shard_map
    for use_pallas in (False, True):
        c = cfg._replace(use_pallas=use_pallas)
        st = init_state(x, init_idx, kern, w)
        step1 = jax.jit(make_step(kern, c))
        dst = jax.device_put(init_dist_state(x[init_idx], kern, w),
                             state_shardings(mesh))
        stepd = jax.jit(make_dist_step(kern, c, mesh))
        key = jax.random.PRNGKey(7)
        for i in range(6):
            key, kb = jax.random.split(key)
            bidx = sample_batch(kb, x.shape[0], cfg.batch_size)
            st, i1 = step1(st, x, bidx)
            dst, i2 = stepd(dst, x[bidx])
            assert abs(float(i1.f_before) - float(i2.f_before)) < 1e-5, \\
                (use_pallas, i)
            assert abs(float(i1.f_after) - float(i2.f_after)) < 1e-5, \\
                (use_pallas, i)
        np.testing.assert_allclose(np.asarray(st.sqnorm),
                                   np.asarray(dst.sqnorm), atol=1e-5)

    # multi-pod style 3-axis mesh also works
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    dst3 = jax.device_put(init_dist_state(x[init_idx], kern, w),
                          state_shardings(mesh3))
    stepd3 = jax.jit(make_dist_step(kern, cfg, mesh3,
                                    data_axes=("pod", "data")))
    dst3, i3 = stepd3(dst3, x[sample_batch(jax.random.PRNGKey(1),
                                           x.shape[0], cfg.batch_size)])
    assert np.isfinite(float(i3.f_before))

    # fit_distributed end-to-end over a host stream
    def stream():
        key = jax.random.PRNGKey(3)
        while True:
            key, kb = jax.random.split(key)
            yield x[sample_batch(kb, x.shape[0], cfg.batch_size)]
    state, hist = fit_distributed(stream(), x[init_idx], kern,
                                  cfg._replace(max_iters=10), mesh,
                                  early_stop=False)
    assert len(hist) == 10
    assert hist[-1]["f_before"] < hist[0]["f_before"]
    print("DISTRIBUTED-OK")
"""


ONDEVICE_FIT = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import KernelKMeans, SolverConfig
    from repro.core import Gaussian
    from repro.data import blobs

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    x, _ = blobs(n=2048, d=16, k=8, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(2.0))
    cfg = SolverConfig(k=8, batch_size=128, tau=64, max_iters=15,
                       epsilon=-1.0, kernel=kern, cache="none",
                       distribution="sharded", jit=True)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100

    # whole early-stopped loop on-device: dataset sharded, batches sampled
    # shard-locally, zero per-step host sync
    est = KernelKMeans(cfg, mesh=mesh).fit(x, 3, init_idx=init_idx)
    dst = est.state_
    assert int(est.iters_) == cfg.max_iters
    assert bool(jnp.all(jnp.isfinite(dst.sqnorm)))
    assert float(jnp.sum(dst.counts)) == cfg.batch_size * cfg.max_iters

    # early stopping still terminates the on-device loop
    est2 = KernelKMeans(cfg.replace(max_iters=300, epsilon=0.01),
                        mesh=mesh).fit(x, 4, init_idx=init_idx)
    assert int(est2.iters_) < 300

    # sharded serving straight from the distributed state
    pred = est.predict(x[:999])
    assert pred.shape == (999,)
    assert int(jnp.max(pred)) < 8 and int(jnp.min(pred)) >= 0
    print("ONDEVICE-OK")
"""


ENGINE_8DEV = """
    import time
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import KernelKMeans, SolverConfig
    from repro.core import Gaussian
    from repro.data import blobs
    from repro.launch.mesh import make_restart_mesh

    assert len(jax.devices()) == 8
    x, _ = blobs(n=2048, d=16, k=8, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(2.0))
    cfg = SolverConfig(k=8, batch_size=128, tau=64, max_iters=15,
                       epsilon=-1.0, kernel=kern, cache="none",
                       distribution="single", jit=True)

    # restart-sharded engine == unsharded engine, bitwise-comparable
    mesh = make_restart_mesh(4)
    assert mesh.devices.size == 4
    eng = KernelKMeans(cfg.replace(restarts=4), mesh=mesh).fit(x, 0)
    eng0 = KernelKMeans(cfg.replace(restarts=4)).fit(x, 0)
    res, res0 = eng.result_, eng0.result_
    np.testing.assert_allclose(np.asarray(res.objectives),
                               np.asarray(res0.objectives), atol=1e-6)
    assert int(res.best) == int(res0.best)

    # sharded predict == unsharded predict on the same winner
    p = eng.predict(x[:999])
    p0 = eng0.predict(x[:999])
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p0))

    # wall-clock: best-of-4 in one compiled program stays under 2x the
    # single-restart compiled fit's first call (a new single-restart
    # program pays its trace and compile; the engine amortizes its
    # compile across fits)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100
    t0 = time.perf_counter()
    jax.block_until_ready(
        KernelKMeans(cfg).fit(x, 5, init_idx=init_idx).state_)
    t_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(eng.fit(x, 5).result_.objectives)
    t_multi = time.perf_counter() - t0
    ratio = t_multi / t_single
    print(f"R4 vs single ratio: {ratio:.2f}")
    assert ratio < 2.0, (t_multi, t_single)
    print("ENGINE-8DEV-OK")
"""


FULLY_PADDED_SHARDS = """
    import warnings; warnings.simplefilter("ignore", DeprecationWarning)
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import KernelKMeans, SolverConfig
    from repro.core import Gaussian
    from repro.core.distributed import pad_for_mesh

    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    kern = Gaussian(kappa=jnp.float32(1.0))
    # n=10 over 8 shards: L=2 rows per shard, so shards 5..7 are ALL
    # padding (n_valid=10 <= (8-1)*2).  The old clamped sampler bound
    # (clip(n_valid - start, 1, L)) would have drawn pad row 0 of those
    # shards into EVERY batch; pad_for_mesh used to refuse outright.
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(10, 4).astype(np.float32))
    xp, nv = pad_for_mesh(x, mesh, ("data",))
    assert xp.shape[0] == 16 and nv == 10
    cfg = SolverConfig(k=2, batch_size=16, tau=8, max_iters=5,
                       epsilon=-1.0, kernel=kern, cache="none",
                       distribution="sharded", jit=True)

    # (a) pad CONTENT is invisible: two fills, identical trajectories
    ex0 = KernelKMeans(cfg, mesh=mesh).plan_for(10).executor
    out0 = ex0.fit(x, jax.random.PRNGKey(1), pad_fill=0.0)
    exb = KernelKMeans(cfg, mesh=mesh).plan_for(10).executor
    outb = exb.fit(x, jax.random.PRNGKey(1), pad_fill=1e6)
    np.testing.assert_array_equal(np.asarray(out0.state.sqnorm),
                                  np.asarray(outb.state.sqnorm))
    np.testing.assert_array_equal(np.asarray(out0.state.pts),
                                  np.asarray(outb.state.pts))

    # (b) every window point is a REAL dataset row — zero pad rows in any
    # sampled batch
    pts = np.asarray(outb.state.pts).reshape(-1, 4)
    assert np.abs(pts).max() < 1e5

    # (c) fully-padded shards contribute ZERO batch mass: per-step batch
    # size is b_loc * ceil(n / L) = 2 * 5, not the nominal 16
    assert float(jnp.sum(out0.state.counts)) == 2 * 5 * 5

    # (d) cached sharded plan under the same layout: per-shard caches,
    # window ids all real
    cfg_c = cfg.replace(cache="lru", cache_tile=8, cache_capacity=4)
    est_c = KernelKMeans(cfg_c, mesh=mesh).fit(x, key=1)
    ids = np.asarray(est_c.state_.pts[..., 0]).astype(int)
    assert ids.max() < 10
    assert float(jnp.sum(est_c.state_.counts)) == 2 * 5 * 5
    print("FULLY_PADDED_OK")
"""


@pytest.mark.slow
def test_fully_padded_shards_masked_8dev():
    """Regression (pad-row leak): a data shard whose rows are all padding
    used to sample its pad row 0 into every batch via the bottom-clamped
    bound — it must contribute nothing instead."""
    _run(FULLY_PADDED_SHARDS, "FULLY_PADDED_OK")


@pytest.mark.slow
def test_distributed_equivalence_8dev():
    _run(STEP_EQUIVALENCE, "DISTRIBUTED-OK")


@pytest.mark.slow
def test_fit_distributed_jit_8dev():
    _run(ONDEVICE_FIT, "ONDEVICE-OK")


@pytest.mark.slow
def test_engine_8dev_equivalence_and_wallclock():
    out = _run(ENGINE_8DEV, "ENGINE-8DEV-OK")
    assert "ratio" in out
