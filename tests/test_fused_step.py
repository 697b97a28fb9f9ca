"""Streaming fused step subsystem (repro.kernels.fused_step + the
`step`/`precision`/`prefetch` SolverConfig axes).

Three contracts:
* the streaming Pallas kernel (interpret mode) matches the XLA streaming
  fallback to float tolerance across tile/shape sweeps (the fallback is
  itself pinned BIT-exactly to the composed step — that equivalence runs
  across the full plan grid in tests/test_api_grid.py);
* mixed precision (`precision="bf16"`) stays within a fixed relative
  objective gap of the f32 fit on the normalized kernels;
* the perf plumbing — host-loop/stream prefetch bit-identity, and the
  cross-executor compiled-program cache (donated-argnum signatures) that
  keeps repeated fits on one executable.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelKMeans, SolverConfig
from repro.core.kernel_fns import Gaussian, Laplacian, Linear, Polynomial
from repro.core.kernel_fns import diag_of
from repro.data import blobs
from repro.kernels import fused_step as fs
from repro.kernels import ops as kops

GAUSS = Gaussian(kappa=jnp.float32(1.5))
KEY = jax.random.PRNGKey(9)

KERNELS = {
    "gaussian": (Gaussian(kappa=jnp.float32(1.3)),
                 dict(kind="gaussian", p0=1.3)),
    "linear": (Linear(), dict(kind="linear")),
    "polynomial": (Polynomial(bias=jnp.float32(1.0), scale=jnp.float32(2.0),
                              degree=2),
                   dict(kind="polynomial", p0=1.0, p1=2.0, p2=2)),
}


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, jnp.float32)


def _blobs(n=256, d=8, k=4, seed=0):
    x, _ = blobs(n=n, d=d, k=k, seed=seed)
    return jnp.asarray(x)


def _cfg(**kw):
    base = dict(k=4, batch_size=32, tau=16, max_iters=6, epsilon=-1.0,
                kernel=GAUSS)
    base.update(kw)
    return SolverConfig(**base)


# ------------------------------------------------------------ chunk plan
def test_center_chunks_cover_and_never_width_one():
    for k in range(1, 40):
        for kc in (2, 3, 8):
            chunks = fs.center_chunks(k, kc)
            # contiguous cover of [0, k)
            assert chunks[0][0] == 0
            assert sum(kk for _, kk in chunks) == k
            for (a, wa), (b, _) in zip(chunks, chunks[1:]):
                assert a + wa == b
            # bit-identity precondition: no 1-wide slab unless k == 1
            if k > 1:
                assert min(kk for _, kk in chunks) >= 2, (k, kc, chunks)


# ----------------------------------------------- streaming XLA fallback
def _assert_ulp(got, want, maxulp, msg=""):
    got, want = np.asarray(got), np.asarray(want)
    if maxulp:
        np.testing.assert_array_max_ulp(got, want, maxulp=maxulp)
    else:
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32), err_msg=msg)


@pytest.mark.parametrize("kname", ["gaussian", "linear", "polynomial"])
@pytest.mark.parametrize("b,k,w,d,maxulp", [
    (32, 4, 48, 8, 2), (37, 7, 21, 9, 2), (64, 13, 40, 3, 0),
    (32, 4, 64, 8, 0), (37, 7, 64, 9, 0),
])
def test_streaming_xla_bit_identical_to_composed(kname, b, k, w, d, maxulp):
    """The fallback's running argmin/min over >=2-center slabs reproduces
    the composed full-matrix pass BIT-exactly (the property the plan-grid
    equivalence in test_api_grid.py rests on) whenever XLA rounds a slab's
    gemm columns as it rounds them inside the full strip.

    On XLA:CPU (jax 0.9.0) that holds when every gemm's column count is a
    multiple of 64 (W=64 here) or d is tiny (d=3); otherwise the rounding
    of a column depends on the gemm's total column count, not only on the
    column's position (aligning the slabs to 64-column blocks of the strip
    does not restore it).  At the unaligned widths W=48 and W=21 the slab
    cross entries then differ from the strip's in the last bits, and the
    distances by at most 2 ulp (measured there: 1 ulp gaussian, 2 linear,
    2 polynomial).  Those cases hold the multi-slab passes to ``maxulp``
    and the labels to equality; a single slab (kc >= k) is the composed
    pass itself and stays bit-exact everywhere."""
    from repro.core.kernel_fns import kernel_cross

    kern, _ = KERNELS[kname]
    xb = _rand((b, d), 0)
    sup = _rand((k, w, d), 1, 0.7).reshape(k * w, d)
    coef = _rand((k, w), 2, 0.1)
    sq = jnp.abs(_rand((k,), 3))
    diag_b = diag_of(kern, xb)

    # arrays as jit ARGUMENTS, like the real step: a jit over closed-over
    # concrete arrays constant-folds through a different evaluator and
    # the comparison would measure the folder, not the compiled program
    @jax.jit
    def composed(xb, sup, coef, sq, diag_b):
        cross = kernel_cross(kern, xb, sup)
        p = jnp.einsum("bkw,kw->bk", cross.reshape(b, k, w), coef)
        dd = diag_b[:, None] - 2.0 * p + sq[None, :]
        return jnp.min(dd, axis=1), jnp.argmin(dd, axis=1).astype(jnp.int32)

    want_min, want_idx = composed(xb, sup, coef, sq, diag_b)
    for kc in (2, 4, k):
        ulp = 0 if kc >= k else maxulp
        assign = jax.jit(lambda *a, kc=kc:
                         fs.streaming_assign_xla(kern, *a, kc=kc))
        got_min, got_idx = assign(xb, sup, coef, sq, diag_b)
        _assert_ulp(got_min, want_min, ulp, msg=f"kc={kc}")
        np.testing.assert_array_equal(np.asarray(got_idx),
                                      np.asarray(want_idx))
        only_min = jax.jit(lambda *a, kc=kc:
                           fs.streaming_min_xla(kern, *a, kc=kc))(
            xb, sup, coef, sq, diag_b)
        _assert_ulp(only_min, want_min, ulp)
        dists = jax.jit(lambda *a, kc=kc:
                        fs.streaming_dists_xla(kern, *a, kc=kc))(
            xb, sup, coef, sq, diag_b)
        assert dists.shape == (b, k)
        _assert_ulp(jnp.min(dists, axis=1), want_min, ulp)


def test_streamed_sqnorm_bit_identical_to_recompute():
    from repro.core.minibatch import _sqnorm_recompute

    x = _rand((512, 8), 0)
    ref = jax.jit(lambda x, idx, coef:
                  _sqnorm_recompute(GAUSS, x, idx, coef))
    for k, w in [(4, 48), (7, 21), (16, 12)]:
        idx = jnp.asarray(
            np.random.default_rng(k).integers(0, 512, (k, w)), jnp.int32)
        coef = _rand((k, w), k + 1, 0.05)
        want = ref(x, idx, coef)
        for kc in (2, 4):
            got = jax.jit(lambda x, idx, coef, kc=kc:
                          fs.streamed_sqnorm(GAUSS, x, idx, coef,
                                             kc=kc))(x, idx, coef)
            np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                          np.asarray(want).view(np.uint32))


# ------------------------------------------------- streaming Pallas kernel
@pytest.mark.parametrize("kname", list(KERNELS))
@pytest.mark.parametrize("b,k,w,d,bt,st", [
    (32, 4, 48, 8, 8, 8),      # several window tiles per center
    (17, 3, 21, 5, 8, 24),     # unaligned everything, one window tile
    (64, 8, 40, 16, 16, 16),   # bt < b, st < w
    (300, 3, 300, 20, None, 256),  # the rule's tile: the whole batch
])
def test_streaming_pallas_interpret_matches_fallback(kname, b, k, w, d,
                                                     bt, st):
    kern, kw = KERNELS[kname]
    xb = _rand((b, d), 0)
    sup = _rand((k, w, d), 1, 0.6)
    coef = _rand((k, w), 2, 0.1)
    sq = jnp.abs(_rand((k,), 3))
    diag_b = diag_of(kern, xb)
    want_min, want_idx = fs.streaming_assign_xla(
        kern, xb, sup.reshape(k * w, d), coef, sq, diag_b)
    got_min, got_idx = fs.streaming_assign_pallas(
        xb, sup, coef, sq, diag_b, bt=bt, st=st, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got_min), np.asarray(want_min),
                               rtol=2e-5, atol=2e-5)
    # distances can tie to the last ulp across implementations; accept an
    # index mismatch only where the two best distances are this close
    idx_ok = np.asarray(got_idx) == np.asarray(want_idx)
    assert np.mean(idx_ok) > 0.99, np.mean(idx_ok)
    if bt is None:
        assert fs.streaming_plans()[(b, w, d)] == (-(-b // 8) * 8, st, 1)


@pytest.mark.parametrize("itemsize", [4, 2])
@pytest.mark.parametrize("b,w,d,want", [
    (2048, 2248, 784, (2048, 256, 1)),    # mnist_rbf: one sweep
    (2048, 4096, 1024, (2048, 256, 1)),   # paper_cluster, one chip
    (17, 21, 5, None),                    # an unaligned per-shard shape
    (8192, 2248, 784, (4096, 128, 2)),    # past the VMEM budget at f32
])
def test_streaming_tiles(b, w, d, want, itemsize):
    bt, st, sweeps, vmem = fs.streaming_tiles(b, w, d, itemsize)
    sub = 8 * 4 // itemsize
    r128 = -(-b // 128) * 128
    assert bt % sub == 0 and sweeps == -(-b // bt)
    assert b <= bt * sweeps <= r128       # never more padding than bt=128
    assert -(-w // st) * st == -(-w // 128) * 128
    assert vmem <= fs._VMEM_BUDGET
    assert vmem == fs._vmem_bytes(bt, st, -(-d // 128) * 128, itemsize)
    if want is None:                      # one tile, the padded batch
        assert (bt, sweeps) == (-(-b // sub) * sub, 1)
    elif itemsize == 4:
        assert (bt, st, sweeps) == want
    else:                                 # half the bytes: no more sweeps
        assert sweeps <= want[2]


def test_streaming_pallas_bf16_mode_close_to_f32():
    kern, kw = KERNELS["gaussian"]
    xb = _rand((24, 16), 0)
    sup = _rand((3, 20, 16), 1, 0.6)
    coef = _rand((3, 20), 2, 0.1)
    sq = jnp.abs(_rand((3,), 3))
    diag_b = diag_of(kern, xb)
    want, _ = fs.streaming_assign_xla(kern, xb, sup.reshape(60, 16), coef,
                                      sq, diag_b)
    got, _ = fs.streaming_assign_pallas(xb, sup, coef, sq, diag_b, bt=8,
                                        st=8, bf16=True, interpret=True,
                                        **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_ops_streaming_dispatch_cpu_uses_fallback():
    """On the CPU backend the dispatcher must route to the bit-identical
    XLA fallback, never interpret-mode Pallas (slow AND not bit-exact)."""
    xb = _rand((16, 4), 0)
    sup = _rand((4, 12, 4), 1)
    coef = _rand((4, 12), 2, 0.1)
    sq = jnp.abs(_rand((4,), 3))
    diag_b = diag_of(GAUSS, xb)
    got = kops.streaming_assign(GAUSS, xb, sup.reshape(48, 4), coef, sq,
                                diag_b)
    want = fs.streaming_assign_xla(GAUSS, xb, sup.reshape(48, 4), coef,
                                   sq, diag_b)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# --------------------------------------------------- config axis plumbing
def test_step_axis_validation_and_resolution():
    with pytest.raises(ValueError):
        _cfg(step="tiled")
    with pytest.raises(ValueError):
        _cfg(precision="fp8")
    # auto resolves to a concrete impl ('composed' off-TPU) and mb_config
    # carries it
    r = _cfg().resolve(n=256)
    assert r.step in ("composed", "fused")
    assert r.mb_config().step == r.step
    assert _cfg(step="fused").mb_config().step == "fused"
    # precision lowers to the kernel-eval compute dtype
    assert _cfg(precision="bf16").mb_config().compute_dtype == "bfloat16"
    assert _cfg().mb_config().compute_dtype == "float32"
    # non-default algorithm modes keep auto on the composed chain
    assert _cfg(sqnorm_mode="incremental").resolve(n=256).step == "composed"


def test_fused_step_rejects_non_recompute_modes():
    from repro.core.minibatch import make_step

    mb = _cfg(step="fused", sqnorm_mode="incremental").mb_config()
    with pytest.raises(ValueError, match="fused"):
        make_step(GAUSS, mb)


# -------------------------------------------------- bf16 quality bounds
@pytest.mark.parametrize("step", ["fused", "composed"])
@pytest.mark.parametrize("kernel", [Gaussian(kappa=jnp.float32(2.0)),
                                    Laplacian(kappa=jnp.float32(2.0))])
def test_bf16_objective_within_relative_gap(kernel, step):
    """Schwartzman'23 regime: bf16 kernel evals with f32 accumulation
    leave the fitted objective within a small relative gap of f32 — on
    the fused step AND the composed chain (the axis must not be inert
    anywhere)."""
    x = _blobs(n=512, d=8, k=4, seed=1)
    kw = dict(kernel=kernel, cache="none", distribution="single",
              jit=False, step=step, max_iters=12)
    f32 = KernelKMeans(_cfg(**kw)).fit(x, KEY)
    b16 = KernelKMeans(_cfg(precision="bf16", **kw)).fit(x, KEY)
    o32, o16 = -f32.score(x), -b16.score(x)
    assert o32 > 0
    assert abs(o16 - o32) / o32 < 0.05, (o32, o16)
    # bf16 actually changed the kernel evals (the axis is live): the
    # trajectories must not be bitwise identical to f32
    assert not np.array_equal(np.asarray(f32.state_.sqnorm),
                              np.asarray(b16.state_.sqnorm))


def test_bf16_never_touches_index_data():
    """Regression: index-data kernels carry row ids as data — the bf16
    cast must be skipped for them on EVERY plan (ids >256 round under
    bf16 and gather the wrong Gram rows).  precision='bf16' on the
    precomputed plan is therefore exactly the f32 fit, bit for bit,
    under both step impls; likewise on a sharded Precomputed fit."""
    x = _blobs(n=512, d=8, k=4, seed=2)
    for step in ("fused", "composed"):
        kw = dict(cache="precomputed", distribution="single", jit=True,
                  step=step)
        f32 = KernelKMeans(_cfg(**kw)).fit(x, KEY)
        b16 = KernelKMeans(_cfg(precision="bf16", **kw)).fit(x, KEY)
        for f in ("idx", "coef", "sqnorm", "counts"):
            np.testing.assert_array_equal(
                np.asarray(getattr(f32.state_, f)),
                np.asarray(getattr(b16.state_, f)),
                err_msg=f"{step}:{f}")
    # sharded plan driven with an explicit Precomputed kernel
    from repro.core.kernel_fns import kernel_cross, Precomputed

    pk = Precomputed(gram=kernel_cross(GAUSS, x, x))
    xi = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    for step in ("fused", "composed"):
        kw = dict(kernel=pk, cache="none", distribution="sharded",
                  jit=True, step=step)
        f32 = KernelKMeans(_cfg(**kw), mesh=mesh).fit(xi, KEY)
        b16 = KernelKMeans(_cfg(precision="bf16", **kw),
                           mesh=mesh).fit(xi, KEY)
        for f in ("pts", "coef", "sqnorm", "counts"):
            np.testing.assert_array_equal(
                np.asarray(getattr(f32.state_, f)),
                np.asarray(getattr(b16.state_, f)),
                err_msg=f"sharded:{step}:{f}")


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                    # pragma: no cover
    HAVE_HYP = False


if HAVE_HYP:
    @settings(max_examples=5, deadline=None)
    @given(kappa=st.floats(0.5, 4.0), seed=st.integers(0, 2 ** 16))
    def test_bf16_objective_gap_property(kappa, seed):
        x = _blobs(n=256, d=8, k=4, seed=seed % 7)
        kern = Gaussian(kappa=jnp.float32(kappa))
        kw = dict(kernel=kern, cache="none", distribution="single",
                  jit=False, step="fused", max_iters=6)
        f32 = KernelKMeans(_cfg(**kw)).fit(x, jax.random.PRNGKey(seed))
        b16 = KernelKMeans(_cfg(precision="bf16", **kw)).fit(
            x, jax.random.PRNGKey(seed))
        o32, o16 = -f32.score(x), -b16.score(x)
        assert abs(o16 - o32) / max(o32, 1e-6) < 0.08


# ------------------------------------------------------ prefetch pipeline
@pytest.mark.parametrize("sampler", ["iid", "nested"])
def test_host_prefetch_bit_identical(sampler):
    """One-deep host-loop prefetch: same states, same history, same
    CARRIED KEY (partial_fit resumption must not see the prefetched
    draw) — with and without early stopping."""
    x = _blobs()
    for eps in (-1.0, 5e-3):          # never-stop and early-stop paths
        kw = dict(cache="none", distribution="single", jit=False,
                  sampler=sampler, epsilon=eps, max_iters=10)
        off = KernelKMeans(_cfg(prefetch=False, **kw)).fit(x, KEY)
        on = KernelKMeans(_cfg(prefetch=True, **kw)).fit(x, KEY)
        for f in ("idx", "coef", "sqnorm", "counts"):
            np.testing.assert_array_equal(
                np.asarray(getattr(off.state_, f)),
                np.asarray(getattr(on.state_, f)), err_msg=f)
        assert off.history_ == on.history_
        np.testing.assert_array_equal(np.asarray(off._outcome.key),
                                      np.asarray(on._outcome.key))


def test_sharded_host_prefetch_bit_identical():
    """The ROADMAP async-prefetch item: double-buffered device_put on the
    sharded jit=False plan is bit-identical to the blocking path."""
    x = _blobs()
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    kw = dict(cache="none", distribution="sharded", jit=False,
              max_iters=8)
    off = KernelKMeans(_cfg(prefetch=False, **kw), mesh=mesh).fit(x, KEY)
    on = KernelKMeans(_cfg(prefetch=True, **kw), mesh=mesh).fit(x, KEY)
    for f in ("pts", "coef", "sqnorm", "counts", "head"):
        np.testing.assert_array_equal(np.asarray(getattr(off.state_, f)),
                                      np.asarray(getattr(on.state_, f)),
                                      err_msg=f)
    assert off.history_ == on.history_


# ------------------------------------- program cache / donation signatures
def test_repeated_fit_reuses_one_executable():
    """Donation audit regression: a FRESH estimator of the same config on
    same-shape data must re-bind nothing — the donated-argnum-keyed
    program registry hands back the already-compiled executable, and the
    jit cache underneath holds exactly one entry."""
    from repro.api import executors as ex

    x = _blobs()
    cfg = _cfg(cache="none", distribution="single", jit=True)
    e1 = KernelKMeans(cfg)
    e1.fit(x, KEY)
    run = e1.plan_.executor._jit_run("init", cfg.max_iters)
    builds = ex.program_builds()
    e2 = KernelKMeans(cfg)
    e2.fit(x, jax.random.PRNGKey(3))           # different key, same shapes
    assert ex.program_builds() == builds, "fresh estimator re-bound"
    assert e2.plan_.executor._jit_run("init", cfg.max_iters) is run
    assert run._cache_size() == 1
    for f in ("coef", "sqnorm"):
        assert np.isfinite(np.asarray(getattr(e2.state_, f))).all()


def test_partial_fit_resume_donates_and_reuses():
    """The resume program donates the FitCarry buffers and is reused
    across partial_fit calls (one executable, one jit entry)."""
    x = _blobs()
    cfg = _cfg(cache="none", distribution="single", jit=True, max_iters=4)
    est = KernelKMeans(cfg)
    est.fit(x, KEY)
    est.partial_fit(x, iters=3)
    run = est.plan_.executor._jit_run("resume", 3)
    assert run._cache_size() == 1
    est.partial_fit(x, iters=3)
    assert run._cache_size() == 1
    # equivalence with one long fit still holds under donation
    ref = KernelKMeans(cfg.replace(max_iters=10)).fit(x, KEY)
    two = KernelKMeans(cfg).fit(x, KEY).partial_fit(x, iters=3) \
                                       .partial_fit(x, iters=3)
    np.testing.assert_array_equal(np.asarray(ref.state_.coef),
                                  np.asarray(two.state_.coef))


# ----------------------------------------------------- 8-dev equivalence
FUSED_8DEV = """
    import warnings; warnings.simplefilter("ignore", DeprecationWarning)
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import KernelKMeans, SolverConfig
    from repro.core import Gaussian
    from repro.data import blobs

    assert len(jax.devices()) == 8, jax.devices()
    kern = Gaussian(kappa=jnp.float32(2.0))
    x, _ = blobs(n=2048, d=16, k=8, seed=0)
    x = jnp.asarray(x)
    key = jax.random.PRNGKey(7)
    base = dict(k=8, batch_size=128, tau=64, max_iters=6, epsilon=-1.0,
                kernel=kern, cache="none", distribution="sharded",
                jit=True)

    # sharded plan on a 4x2 data x model mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    ec = KernelKMeans(SolverConfig(step="composed", **base),
                      mesh=mesh).fit(x, key)
    ef = KernelKMeans(SolverConfig(step="fused", **base),
                      mesh=mesh).fit(x, key)
    for f in ("pts", "coef", "sqnorm", "counts", "head"):
        np.testing.assert_array_equal(np.asarray(getattr(ec.state_, f)),
                                      np.asarray(getattr(ef.state_, f)),
                                      err_msg=f)
    assert int(ec.iters_) == int(ef.iters_)

    # fused restart x data x model plan on a 2x2x2 mesh
    fmesh = jax.make_mesh((2, 2, 2), ("restart", "data", "model"))
    rc = KernelKMeans(SolverConfig(restarts=4, step="composed", **base),
                      mesh=fmesh).fit(x, key)
    rf = KernelKMeans(SolverConfig(restarts=4, step="fused", **base),
                      mesh=fmesh).fit(x, key)
    assert rf.plan_.name == "fused_restart_sharded"
    np.testing.assert_array_equal(np.asarray(rc.result_.objectives),
                                  np.asarray(rf.result_.objectives))
    np.testing.assert_array_equal(np.asarray(rc.result_.iters),
                                  np.asarray(rf.result_.iters))
    for f in ("pts", "coef", "sqnorm", "counts", "head"):
        np.testing.assert_array_equal(np.asarray(getattr(rc.state_, f)),
                                      np.asarray(getattr(rf.state_, f)),
                                      err_msg=f)

    # prefetch on the multi-shard host-driven plan
    off = KernelKMeans(SolverConfig(jit=False, prefetch=False, **{
        k: v for k, v in base.items() if k != "jit"}),
        mesh=mesh).fit(x, key)
    on = KernelKMeans(SolverConfig(jit=False, prefetch=True, **{
        k: v for k, v in base.items() if k != "jit"}),
        mesh=mesh).fit(x, key)
    for f in ("pts", "coef", "sqnorm", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(off.state_, f)),
                                      np.asarray(getattr(on.state_, f)),
                                      err_msg=f)
    assert off.history_ == on.history_
    print("FUSED_STEP_8DEV_OK")
"""


@pytest.mark.slow
def test_fused_step_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c",
                        textwrap.dedent(FUSED_8DEV)],
                       env=env, capture_output=True, text=True,
                       timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FUSED_STEP_8DEV_OK" in r.stdout, r.stdout[-2000:]
