"""Gram tile cache subsystem (repro.cache): LRU correctness, cached vs
uncached numerical equivalence for fit / predict / the distributed path,
the Pallas gather-from-cache kernel, the nested sampler, and the
deterministic-resume pipeline regression."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import (
    as_kernel, create_cache, cross_update, make_cached, precompute_gram,
    predict_cached, stats, warm, warm_rows,
)
from repro.core import MBConfig, fit, predict
from repro.core.kernel_fns import (
    Gaussian, Laplacian, Linear, Polynomial, diag_is_one, kernel_cross,
    kernel_diag,
)
from repro.core.minibatch import fit_cached, sample_batch_nested
from repro.data.pipeline import ClusterBatchPipeline

KERNELS = [
    Gaussian(kappa=jnp.float32(1.7)),
    Laplacian(kappa=jnp.float32(2.3)),
    Polynomial(bias=jnp.float32(1.0), scale=jnp.float32(4.0), degree=2),
    Linear(),
]


def _data(n=64, d=5, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)),
                       jnp.float32)


# ------------------------------------------------------------- LRU mechanics
def test_lru_eviction_order():
    x = _data(48)
    base = Gaussian(kappa=jnp.float32(1.0))
    c = create_cache(48, tile=8, capacity=3)
    c = warm(c, base, x, jnp.arange(0, 8))     # block 0
    c = warm(c, base, x, jnp.arange(8, 16))    # block 1
    c = warm(c, base, x, jnp.arange(16, 24))   # block 2 -> full
    assert sorted(np.asarray(c.keys).tolist()) == [0, 1, 2]
    c = warm(c, base, x, jnp.arange(0, 8))     # touch 0: now LRU is 1
    c = warm(c, base, x, jnp.arange(24, 32))   # block 3 evicts block 1
    assert sorted(np.asarray(c.keys).tolist()) == [0, 2, 3]
    assert int(c.evictions) == 1
    assert int(c.misses) == 4 and int(c.hits) == 1


def test_capacity_one_thrash_is_correct():
    x = _data(32)
    base = Polynomial(bias=jnp.float32(0.5), scale=jnp.float32(2.0),
                      degree=2)
    ck, xi = make_cached(base, x, tile=8, capacity=1)
    ridx = jnp.asarray([0, 9, 17, 25, 3, 11], jnp.int32)  # 4 distinct blocks
    cidx = jnp.arange(32, dtype=jnp.int32)
    out, ck = cross_update(ck, xi[ridx], xi[cidx])
    # cached tiles come from (tile, n) gemms, the reference from one
    # (6, 32) gemm: f32 dot products of different shapes may sum in a
    # different order.  Kernel values here reach ~39, where one f32 ulp is
    # ~4e-6, so the bound is relative (~8 ulps), not a fixed 1e-6.
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kernel_cross(base, x[ridx],
                                                       x[cidx])),
                               rtol=1e-6, atol=1e-6)
    s = stats(ck.cache)
    assert s["resident"] == 1 and s["capacity"] == 1
    assert s["misses"] == 4                     # every distinct block missed
    # repeat: capacity-1 cannot retain a 4-block working set -> thrash again
    out2, ck = cross_update(ck, xi[ridx], xi[cidx])
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out), atol=0)
    assert stats(ck.cache)["misses"] >= 7


def test_tile_must_divide_rows():
    with pytest.raises(ValueError):
        create_cache(100, tile=33, capacity=2)


# -------------------------------------------------- cross-kernel equivalence
@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: type(k).__name__)
def test_cached_cross_matches_direct(kern):
    x = _data(64, 6, seed=3)
    ck, xi = make_cached(kern, x, tile=16, capacity=2)
    rng = np.random.default_rng(5)
    ridx = jnp.asarray(rng.integers(0, 64, 23), jnp.int32)
    cidx = jnp.asarray(rng.integers(0, 64, 11), jnp.int32)
    want = kernel_cross(kern, x[ridx], x[cidx])
    got_stateful, ck = cross_update(ck, xi[ridx], xi[cidx])
    got_readonly = kernel_cross(ck, xi[ridx], xi[cidx])  # dispatch adapter
    np.testing.assert_allclose(np.asarray(got_stateful), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_readonly), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel_diag(ck, xi[ridx])),
                               np.asarray(kernel_diag(kern, x[ridx])),
                               atol=1e-6)


def test_cached_cross_bfloat16_store():
    kern = Gaussian(kappa=jnp.float32(1.0))
    x = _data(32, 4, seed=9)
    ck, xi = make_cached(kern, x, tile=8, capacity=4, dtype=jnp.bfloat16)
    ridx = jnp.arange(32, dtype=jnp.int32)
    got, _ = cross_update(ck, xi[ridx], xi[ridx])
    want = kernel_cross(kern, x, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-2)


def test_diag_is_one_flags():
    assert diag_is_one(Gaussian(kappa=jnp.float32(1.0)))
    assert diag_is_one(Laplacian(kappa=jnp.float32(1.0)))
    assert not diag_is_one(Linear())
    x = _data(16, 3)
    ck_g, _ = make_cached(Gaussian(kappa=jnp.float32(1.0)), x, tile=4,
                          capacity=2)
    ck_l, _ = make_cached(Linear(), x, tile=4, capacity=2)
    assert diag_is_one(ck_g) and not diag_is_one(ck_l)


def test_precomputed_gram_matches_direct():
    kern = Gaussian(kappa=jnp.float32(0.8))
    x = _data(40, 7, seed=2)
    pk, xi = as_kernel(precompute_gram(kern, x, block=16))
    # the Gram is built from (16, n) gemm blocks, the reference from one
    # (n, n) gemm, so the squared distance inside exp() may differ in its
    # last ulps through f32 reduction order.  |x|^2 reaches ~19 here (ulp
    # ~2e-6), and exp(-d2 / 0.8) passes that on scaled by up to 1.25.
    tol = 1e-5
    np.testing.assert_allclose(np.asarray(pk.gram),
                               np.asarray(kernel_cross(kern, x, x)),
                               atol=tol)
    ridx = jnp.asarray([3, 17, 39, 0], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(kernel_cross(pk, xi[ridx], xi)),
        np.asarray(kernel_cross(kern, x[ridx], x)), atol=tol)


# --------------------------------------------------------- fit / predict
@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "pallas_gather"])
def test_fit_cached_matches_fit(use_pallas):
    from repro.data import blobs

    x, _ = blobs(n=256, d=8, k=4, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(1.5))
    cfg = MBConfig(k=4, batch_size=32, tau=16, max_iters=8, epsilon=-1.0,
                   use_pallas=use_pallas)
    init_idx = jnp.array([0, 60, 120, 180], jnp.int32)
    st_u, hu = fit(x, kern, cfg, jax.random.PRNGKey(3), init_idx=init_idx,
                   early_stop=False)
    st_c, hc, ck = fit_cached(x, kern, cfg, jax.random.PRNGKey(3),
                              tile=32, capacity=8, init_idx=init_idx,
                              early_stop=False)
    assert len(hu) == len(hc)
    np.testing.assert_array_equal(np.asarray(st_u.idx), np.asarray(st_c.idx))
    np.testing.assert_allclose(np.asarray(st_u.sqnorm),
                               np.asarray(st_c.sqnorm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_u.coef),
                               np.asarray(st_c.coef), atol=1e-5)
    for a, b in zip(hu, hc):
        assert a["f_after"] == pytest.approx(b["f_after"], abs=1e-5)

    xi = jnp.arange(256, dtype=jnp.float32)[:, None]
    pu = np.asarray(predict(st_u, x, x, kern))
    pc = np.asarray(predict(st_c, xi, xi, ck))
    np.testing.assert_array_equal(pu, pc)
    lab, ck2 = predict_cached(ck, st_c, jnp.arange(256), chunk=64)
    np.testing.assert_array_equal(np.asarray(lab), pu)
    s = stats(ck2.cache)
    assert s["hits"] > 0 and s["hit_rate"] > 0.5


@pytest.fixture(scope="module")
def covered_cache_run():
    """A cached fit (nested sampler) and one cached predict pass over a
    4x repeated-row query stream, with a capacity that covers every row
    block (n / tile = 16 blocks, 16 slots)."""
    from repro.api import KernelKMeans, SolverConfig
    from repro.core.state import window_size
    from repro.data import blobs

    n, d, k, b, tau, iters = 2048, 16, 8, 256, 64, 10
    tile, capacity, reps = n // 16, 16, 4
    x, _ = blobs(n=n, d=d, k=k, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(1.0))
    est = KernelKMeans(SolverConfig(
        k=k, batch_size=b, tau=tau, max_iters=iters, epsilon=-1.0,
        kernel=kern, cache="lru", cache_tile=tile, cache_capacity=capacity,
        sampler="nested", distribution="single", jit=False))
    est.fit(x, jax.random.PRNGKey(0),
            init_idx=jnp.arange(k, dtype=jnp.int32) * (n // k))
    qidx = jnp.tile(jnp.arange(n, dtype=jnp.int32), reps)
    labels, ck = predict_cached(est.cache_, est.state_, qidx)
    return dict(x=x, kern=kern, st=est.state_, qidx=qidx, labels=labels,
                stats=stats(ck.cache), n=n, tile=tile, capacity=capacity,
                steps=len(est.history_), b=b, k=k, w=window_size(b, tau))


def test_covering_capacity_never_evicts(covered_cache_run):
    """With capacity >= n / tile no block is ever evicted, so every
    read-through access after a block's warm is a hit and the counted
    misses are the complete kernel-evaluation count."""
    r = covered_cache_run
    assert r["capacity"] >= r["n"] // r["tile"]
    assert r["stats"]["evictions"] == 0


def test_cached_predict_labels_match_direct_predict(covered_cache_run):
    r = covered_cache_run
    direct = predict(r["st"], r["x"], r["x"][r["qidx"]], r["kern"])
    np.testing.assert_array_equal(np.asarray(r["labels"]),
                                  np.asarray(direct))


def test_cached_evals_below_uncached_count(covered_cache_run):
    """Counted kernel evaluations of the cached fit + predict fall below
    the analytic uncached count: per step b*kW assignment + k*W^2
    recompute + b*kW direct evaluation, per query kW."""
    r = covered_cache_run
    kw = r["k"] * r["w"]
    uncached = (r["steps"] * (2 * r["b"] * kw + r["k"] * r["w"] ** 2)
                + int(r["qidx"].shape[0]) * kw)
    assert 0 < r["stats"]["evals"] < uncached


def test_predict_cached_counters_all_hits_when_warm():
    kern = Gaussian(kappa=jnp.float32(1.0))
    x = _data(64, 4, seed=7)
    ck, xi = make_cached(kern, x, tile=16, capacity=4)
    ck = warm_rows(ck, jnp.arange(64))
    from repro.core.state import init_state
    state = init_state(xi, jnp.array([1, 33], jnp.int32), ck, window=8)
    _, ck = predict_cached(ck, state, jnp.arange(64), chunk=32)
    before = stats(ck.cache)["misses"]
    _, ck = predict_cached(ck, state, jnp.arange(64), chunk=32)
    assert stats(ck.cache)["misses"] == before   # fully resident: no misses


def test_nested_sampler_reuse_and_determinism():
    key = jax.random.PRNGKey(0)
    b1 = sample_batch_nested(key, 5, 512, 64, reuse=0.5, refresh=8)
    b1b = sample_batch_nested(key, 5, 512, 64, reuse=0.5, refresh=8)
    b2 = sample_batch_nested(key, 6, 512, 64, reuse=0.5, refresh=8)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b1b))
    assert b1.shape == (64,)
    assert int(jnp.min(b1)) >= 0 and int(jnp.max(b1)) < 512
    # staggered refresh: consecutive steps share all but ~m/refresh of the
    # reused prefix
    overlap = int(jnp.sum(b1[:32] == b2[:32]))
    assert overlap >= 32 - (32 // 8) - 1


def test_engine_share_eval_gram_equivalence():
    from repro.core.engine import fit_restarts
    from repro.data import blobs

    x, _ = blobs(n=256, d=8, k=4, seed=1)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(1.0))
    cfg = MBConfig(k=4, batch_size=32, tau=16, max_iters=6, epsilon=-1.0)
    r_on = fit_restarts(x, kern, cfg, jax.random.PRNGKey(2), restarts=3,
                        share_eval_gram=True)
    r_off = fit_restarts(x, kern, cfg, jax.random.PRNGKey(2), restarts=3,
                         share_eval_gram=False)
    np.testing.assert_allclose(np.asarray(r_on.objectives),
                               np.asarray(r_off.objectives), atol=1e-5)
    assert int(r_on.best) == int(r_off.best)


def test_cached_gather_pallas_matches_ref():
    from repro.kernels import ops, ref

    rng = np.random.default_rng(11)
    for b, n, k, w, bt, nt in [(5, 40, 3, 7, 8, 8), (16, 64, 2, 16, 8, 16)]:
        rows = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, n, (k, w)), jnp.int32)
        coef = jnp.asarray(rng.normal(size=(k, w)), jnp.float32)
        want = ref.cached_assign_dots(rows, ids, coef)
        got = ops.cached_assign_dots(rows, ids, coef, bt=bt, nt=nt,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# --------------------------------------------- pipeline deterministic resume
@pytest.mark.parametrize("mode", ["iid", "nested"])
def test_pipeline_deterministic_resume(mode):
    """Same (seed, step) -> same batch after restart: a fresh pipeline
    instance reproduces the stream exactly from any step."""
    x = np.random.default_rng(0).normal(size=(128, 4))
    p1 = ClusterBatchPipeline(x, batch=16, seed=42, mode=mode)
    want = [p1(s) for s in range(12)]
    p2 = ClusterBatchPipeline(x, batch=16, seed=42, mode=mode)  # "restart"
    for s in (11, 3, 7, 0):
        np.testing.assert_array_equal(p2(s), want[s])
    it = iter(ClusterBatchPipeline(x, batch=16, seed=42, mode=mode))
    np.testing.assert_array_equal(next(it), want[0])
    np.testing.assert_array_equal(next(it), want[1])


def test_pipeline_nested_reuses_rows():
    x = np.random.default_rng(1).normal(size=(256, 4))
    p = ClusterBatchPipeline(x, batch=32, seed=0, mode="nested",
                             reuse=0.5, refresh=8)
    i5, i6 = p.batch_indices(5), p.batch_indices(6)
    assert np.sum(i5[:16] == i6[:16]) >= 16 - (16 // 8) - 1
    uniq = {tuple(p.batch_indices(s)) for s in range(6)}
    assert len(uniq) == 6    # tails still differ every step


# ------------------------------------------------------- distributed (slow)
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


DIST_CACHED = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import MBConfig, Gaussian
    from repro.core.distributed import (
        fit_distributed_jit, fit_distributed_cached_jit)
    from repro.cache import stats
    from repro.data import blobs

    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    x, _ = blobs(n=2048, d=16, k=8, seed=0)
    x = jnp.asarray(x)
    kern = Gaussian(kappa=jnp.float32(2.0))
    cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=6, epsilon=-1.0)
    init_idx = jnp.arange(8, dtype=jnp.int32) * 100

    st_u, it_u = fit_distributed_jit(x, x[init_idx], kern, cfg, mesh,
                                     jax.random.PRNGKey(7))
    st_c, caches, it_c = fit_distributed_cached_jit(
        x, init_idx, kern, cfg, mesh, jax.random.PRNGKey(7),
        tile=128, capacity=16)   # covers batch + window working set
    assert int(it_u) == int(it_c)
    np.testing.assert_allclose(np.asarray(st_u.sqnorm),
                               np.asarray(st_c.sqnorm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_u.counts),
                               np.asarray(st_c.counts), atol=0)
    # per-shard caches: shard-local keys, real hits on every shard
    for s in range(4):
        st = stats(jax.tree.map(lambda a: a[s], caches))
        assert st["hits"] > 0 and st["misses"] >= 1, (s, st)
    print("DIST_CACHED_OK")
"""


@pytest.mark.slow
def test_distributed_cached_fit_equivalence():
    _run(DIST_CACHED, "DIST_CACHED_OK")
