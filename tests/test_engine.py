"""Multi-restart engine + single-device shard_map foundation.

Everything here runs in the main pytest process on the single real CPU
device (a 1-device mesh exercises the full shard_map machinery — specs,
collectives over size-1 axes); the 8-virtual-device variants
live in test_distributed.py subprocesses.  No hypothesis dependency: these
parametrized sweeps are the always-on fast lane of the invariant coverage.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Gaussian, MBConfig, MultiRestartEngine, batch_objective, fit, fit_jit,
    fit_restarts, init_state, make_step, predict, window_size,
)
from repro.core.distributed import (
    fit_distributed_jit, init_dist_state, make_dist_step,
    predict_distributed, state_shardings,
)
from repro.core.engine import make_restart_run
from repro.core.minibatch import sample_batch
from repro.data import blobs

GAUSS = Gaussian(kappa=jnp.float32(2.0))


def _blobs(n=1024, d=16, k=8, seed=0):
    x, _ = blobs(n=n, d=d, k=k, seed=seed)
    return jnp.asarray(x)


def _mesh1():
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])


# ------------------------------------------------- shard_map on one device
def test_single_device_shardmap_step_matches_make_step():
    """jax.shard_map on a (1,1) mesh == the plain single-device step,
    trajectory-for-trajectory."""
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=8, epsilon=-1.0)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100
    w = window_size(cfg.batch_size, cfg.tau)
    mesh = _mesh1()

    st = init_state(x, init_idx, GAUSS, w)
    step1 = jax.jit(make_step(GAUSS, cfg))
    dst = jax.device_put(init_dist_state(x[init_idx], GAUSS, w),
                         state_shardings(mesh))
    stepd = jax.jit(make_dist_step(GAUSS, cfg, mesh))

    key = jax.random.PRNGKey(7)
    for i in range(5):
        key, kb = jax.random.split(key)
        bidx = sample_batch(kb, x.shape[0], cfg.batch_size)
        st, i1 = step1(st, x, bidx)
        dst, i2 = stepd(dst, x[bidx])
        assert float(i1.f_before) == pytest.approx(float(i2.f_before),
                                                   abs=1e-5), i
        assert float(i1.f_after) == pytest.approx(float(i2.f_after),
                                                  abs=1e-5), i
    np.testing.assert_allclose(np.asarray(st.sqnorm), np.asarray(dst.sqnorm),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(st.counts), np.asarray(dst.counts),
                               atol=0)


def test_single_device_shardmap_fit_matches_fit_jit():
    """Driving the 1-device-mesh dist step with fit_jit's exact PRNG stream
    reproduces fit_jit's final state."""
    x = _blobs(n=800)
    cfg = MBConfig(k=4, batch_size=64, tau=32, max_iters=10, epsilon=-1.0)
    init_idx = jnp.array([0, 100, 200, 300], jnp.int32)
    w = window_size(cfg.batch_size, cfg.tau)
    mesh = _mesh1()

    st_jit, iters = fit_jit(x, GAUSS, cfg, jax.random.PRNGKey(11), init_idx)
    assert int(iters) == cfg.max_iters

    dst = jax.device_put(init_dist_state(x[init_idx], GAUSS, w),
                         state_shardings(mesh))
    stepd = jax.jit(make_dist_step(GAUSS, cfg, mesh))
    key = jax.random.PRNGKey(11)
    for _ in range(cfg.max_iters):
        key, kb = jax.random.split(key)
        bidx = sample_batch(kb, x.shape[0], cfg.batch_size)
        dst, _ = stepd(dst, x[bidx])
    np.testing.assert_allclose(np.asarray(st_jit.sqnorm),
                               np.asarray(dst.sqnorm), atol=1e-5)


def test_fit_distributed_jit_single_device_runs_and_improves():
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=15, epsilon=-1.0)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100
    mesh = _mesh1()
    dst, iters = fit_distributed_jit(x, x[init_idx], GAUSS, cfg, mesh,
                                     jax.random.PRNGKey(3))
    assert int(iters) == cfg.max_iters
    assert bool(jnp.all(jnp.isfinite(dst.sqnorm)))
    assert float(jnp.sum(dst.counts)) == cfg.batch_size * cfg.max_iters


# --------------------------------------------------------------- the engine
def test_engine_selects_argmin_restart():
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=10, epsilon=-1.0)
    res = fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(0), restarts=3)
    assert res.objectives.shape == (3,)
    assert int(res.best) == int(jnp.argmin(res.objectives))
    assert float(res.objective) == pytest.approx(
        float(jnp.min(res.objectives)))
    assert res.state.idx.shape == (8, window_size(128, 64))
    # all restarts ran to the (no-early-stop) limit
    np.testing.assert_array_equal(np.asarray(res.iters), 10)


def test_engine_deterministic_and_cached_program_consistent():
    x = _blobs(n=512, d=8, k=4)
    cfg = MBConfig(k=4, batch_size=64, tau=32, max_iters=8, epsilon=-1.0)
    eng = MultiRestartEngine(GAUSS, cfg, restarts=2)
    r1 = eng.fit(x, jax.random.PRNGKey(5))
    r2 = eng.fit(x, jax.random.PRNGKey(5))  # second call: cached program
    np.testing.assert_allclose(np.asarray(r1.objectives),
                               np.asarray(r2.objectives), atol=0)
    run = make_restart_run(GAUSS, cfg)
    r3 = fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(5), restarts=2,
                      _run=run)
    np.testing.assert_allclose(np.asarray(r1.objectives),
                               np.asarray(r3.objectives), atol=1e-7)


def test_engine_restart_quality_monotone_vs_single():
    """Best-of-R can only improve on the mean single restart (same cfg)."""
    x = _blobs(n=2000, seed=3)
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=25, epsilon=-1.0)
    res = fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(1), restarts=4)
    assert float(res.objective) <= float(jnp.mean(res.objectives)) + 1e-7


def test_engine_early_stop_per_restart():
    """epsilon > 0: restarts terminate independently inside the vmapped
    while_loop (iters may differ per lane, all <= max_iters)."""
    x = _blobs(n=2000)
    cfg = MBConfig(k=8, batch_size=512, tau=128, max_iters=200, epsilon=0.01)
    res = fit_restarts(x, Gaussian(kappa=jnp.float32(1.0)), cfg,
                       jax.random.PRNGKey(2), restarts=3)
    iters = np.asarray(res.iters)
    assert (iters < 200).all()
    assert (iters >= 1).all()


def test_engine_random_init_and_explicit_init_idx():
    x = _blobs(n=512, d=8, k=4)
    cfg = MBConfig(k=4, batch_size=64, tau=32, max_iters=5, epsilon=-1.0)
    r_rand = fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(0), restarts=2,
                          init="random")
    assert np.isfinite(float(r_rand.objective))
    init_idx = jnp.stack([jnp.arange(4), jnp.arange(4) * 100]).astype(
        jnp.int32)
    r_exp = fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(0), restarts=2,
                         init_idx=init_idx)
    assert np.isfinite(float(r_exp.objective))
    with pytest.raises(ValueError):
        fit_restarts(x, GAUSS, cfg, jax.random.PRNGKey(0), restarts=3,
                     init_idx=init_idx)


def test_engine_predict_matches_minibatch_predict():
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=10, epsilon=-1.0)
    eng = MultiRestartEngine(GAUSS, cfg, restarts=2)
    res = eng.fit(x, jax.random.PRNGKey(0))
    p_eng = eng.predict(x[:200])
    p_ref = predict(res.state, x, x[:200], GAUSS)
    np.testing.assert_array_equal(np.asarray(p_eng), np.asarray(p_ref))


def test_predict_distributed_single_device_matches_predict():
    """Sharded serving on a 1-device mesh == plain predict, including the
    non-divisible padding path."""
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=8, epsilon=-1.0)
    state, _ = fit(x, GAUSS, cfg, jax.random.PRNGKey(0), early_stop=False)
    mesh = _mesh1()
    for nq in (64, 777):
        got = predict_distributed(state, x, x[:nq], GAUSS, mesh)
        want = predict(state, x, x[:nq], GAUSS)
        assert got.shape == (nq,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batch_objective_matches_step_f_before():
    x = _blobs()
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=3, epsilon=-1.0)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100
    state = init_state(x, init_idx, GAUSS, window_size(128, 64))
    step = jax.jit(make_step(GAUSS, cfg))
    bidx = sample_batch(jax.random.PRNGKey(1), x.shape[0], 128)
    _, info = step(state, x, bidx)
    obj = batch_objective(GAUSS, state, x, bidx)
    assert float(obj) == pytest.approx(float(info.f_before), abs=1e-6)


# ---------------------------------------- mode invariants, hypothesis-free
@pytest.mark.parametrize("b,tau", [(32, 16), (96, 48), (64, 128)])
def test_sqnorm_incremental_matches_recompute_sweep(b, tau):
    x = _blobs(n=384, d=8, k=3, seed=1)
    base = MBConfig(k=3, batch_size=b, tau=tau, max_iters=8, epsilon=-1.0)
    init_idx = jnp.array([0, 50, 100], jnp.int32)
    s_rec, _ = fit(x, GAUSS, base, jax.random.PRNGKey(2), init_idx=init_idx,
                   early_stop=False)
    s_inc, _ = fit(x, GAUSS, base._replace(sqnorm_mode="incremental"),
                   jax.random.PRNGKey(2), init_idx=init_idx,
                   early_stop=False)
    np.testing.assert_allclose(np.asarray(s_inc.sqnorm),
                               np.asarray(s_rec.sqnorm), atol=3e-4)


@pytest.mark.parametrize("b,tau", [(32, 16), (96, 48), (64, 128)])
def test_eval_delta_matches_direct_sweep(b, tau):
    x = _blobs(n=384, d=8, k=3, seed=1)
    base = MBConfig(k=3, batch_size=b, tau=tau, max_iters=8, epsilon=-1.0)
    init_idx = jnp.array([0, 50, 100], jnp.int32)
    _, h_dir = fit(x, GAUSS, base, jax.random.PRNGKey(2), init_idx=init_idx,
                   early_stop=False)
    _, h_del = fit(x, GAUSS, base._replace(eval_mode="delta"),
                   jax.random.PRNGKey(2), init_idx=init_idx,
                   early_stop=False)
    for a, c in zip(h_del, h_dir):
        assert a["f_after"] == pytest.approx(c["f_after"], abs=3e-4)


@pytest.mark.parametrize("b,k,w,d,bt,st", [
    (27, 3, 37, 11, 8, 8),
    (16, 2, 24, 8, 128, 128),   # tiles larger than the problem: clamped
    (64, 4, 48, 16, 16, 32),
])
def test_ops_tile_clamp_matches_reference(b, k, w, d, bt, st):
    """ops.fused_batch_center_dots with clamped per-shard tiles == einsum."""
    from repro.core.minibatch import _batch_center_dots
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(200, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 200, (k, w)), jnp.int32)
    coef = jnp.abs(jnp.asarray(rng.normal(size=(k, w)), jnp.float32)) / w
    xb = x[:b]
    want = _batch_center_dots(GAUSS, xb, x, idx, coef, use_pallas=False)
    got = ops.fused_batch_center_dots(GAUSS, xb, x[idx.reshape(-1)], coef,
                                      bt=bt, st=st, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
