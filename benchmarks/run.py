"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]

Prints ``name,us_per_call,derived`` CSV rows (plus commentary lines starting
with '#').  Mapping to the paper:

  speedup        Fig. 1 (runtime bars): full-batch vs Algorithm 1 vs
                 Algorithm 2 per-iteration wall time; speedup ratios.
  n_independence Thm 1(1): Algorithm 2 iteration time is independent of n
                 (the full-batch baseline scales ~n^2).
  quality        Figs. 2-13: ARI/NMI of all algorithms on matched datasets.
  tau_sweep      Appendix C: quality vs tau in {50,100,200,300}.
  rates          §6 claim 2: beta learning rate vs sklearn rate.
  gamma_table    Table 1: gamma per (dataset x kernel).
  termination    Thm 1(2): iterations-to-stop vs 1/epsilon.
  service        serving gates (docs/serving.md): microbatch p99 vs bare
                 predict, zero recompiles after warmup, snapshot-swap
                 pause — writes BENCH_service.json.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Gaussian, MBConfig, adjusted_rand_index, fit, gamma_of,
    normalized_mutual_info, predict,
)
from repro.core import fullbatch, lloyd, untruncated
from repro.core.minibatch import make_step, sample_batch
from repro.core.state import init_state, window_size
from repro.data import blobs, circles, moons
from repro.data.graph_kernels import heat_kernel, knn_kernel

# a numpy scalar, not a device array: importing this module touches no
# device (the process that runs the benchmarks may own the only chip)
GAUSS = Gaussian(kappa=np.float32(1.0))


def bench_env(seed=0) -> dict:
    """Shared provenance block embedded in every BENCH_*.json ``env`` key:
    enough to tell two result files apart (code version, jax version,
    backend/device, seed) without re-running anything."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=here,
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or None
    except Exception:                       # noqa: BLE001 — no git, no sha
        sha = None
    dev = jax.devices()[0]
    return dict(git_sha=sha, jax_version=jax.__version__,
                backend=jax.default_backend(),
                device_kind=getattr(dev, "device_kind", str(dev)),
                device_count=jax.device_count(), seed=int(seed))


def _time_step(fn, iters=10, warmup=2):
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


# ----------------------------------------------------------------- speedup
def bench_speedup(fast: bool):
    ns = [2048, 8192] if fast else [2048, 8192, 16384]
    k, b, tau, d = 10, 512, 200, 32
    for n in ns:
        x, _ = blobs(n=n, d=d, k=k, seed=0)
        x = jnp.asarray(x)
        cfg = MBConfig(k=k, batch_size=b, tau=tau, max_iters=5,
                       epsilon=-1.0)
        init_idx = jnp.arange(k, dtype=jnp.int32)

        # full batch (the O(n^2) baseline)
        fb_step = jax.jit(fullbatch.make_fullbatch_step(GAUSS, k))
        assign0 = jnp.zeros((n,), jnp.int32)
        t_fb = _time_step(lambda: fb_step(assign0, x)[0], iters=3)

        # Algorithm 1 (DP, O(n(b+k)))
        dp_step = jax.jit(untruncated.make_dp_step(GAUSS, cfg))
        dps = untruncated.init_dp_state(x, init_idx, GAUSS)
        bidx = sample_batch(jax.random.PRNGKey(0), n, b)
        t_dp = _time_step(lambda: dp_step(dps, x, bidx)[0].sqnorm)

        # Algorithm 2 (truncated, O(k(tau+b)^2), n-independent)
        st = init_state(x, init_idx, GAUSS, window_size(b, tau))
        mb_step = jax.jit(make_step(GAUSS, cfg))
        t_mb = _time_step(lambda: mb_step(st, x, bidx)[0].sqnorm)

        print(f"speedup_fullbatch_n{n},{t_fb:.0f},1.0x")
        print(f"speedup_alg1_n{n},{t_dp:.0f},{t_fb / t_dp:.1f}x")
        print(f"speedup_alg2_n{n},{t_mb:.0f},{t_fb / t_mb:.1f}x")


def bench_n_independence(fast: bool):
    k, b, tau, d = 10, 256, 100, 16
    times = []
    ns = [4096, 16384] if fast else [4096, 16384, 65536]
    for n in ns:
        x, _ = blobs(n=n, d=d, k=k, seed=0)
        x = jnp.asarray(x)
        cfg = MBConfig(k=k, batch_size=b, tau=tau, max_iters=5,
                       epsilon=-1.0)
        st = init_state(x, jnp.arange(k, dtype=jnp.int32), GAUSS,
                        window_size(b, tau))
        step = jax.jit(make_step(GAUSS, cfg))
        bidx = sample_batch(jax.random.PRNGKey(0), n, b)
        t = _time_step(lambda: step(st, x, bidx)[0].sqnorm)
        times.append(t)
        print(f"n_independence_n{n},{t:.0f},iter_time_us")
    ratio = times[-1] / times[0]
    print(f"n_independence_ratio,{ratio:.2f},"
          f"~1.0 expected across {ns[-1] // ns[0]}x n growth")


# ----------------------------------------------------------------- quality
def _mb_fit_ari(xj, kern, k, b, tau, rate, y, seed, iters=80):
    from repro.api import KernelKMeans, SolverConfig

    cfg = SolverConfig(k=k, batch_size=b, tau=tau, rate=rate,
                       max_iters=iters, epsilon=-1.0, kernel=kern,
                       cache="none", distribution="single", jit=False)
    est = KernelKMeans(cfg).fit(xj, key=jax.random.PRNGKey(seed))
    pred = np.asarray(est.predict(xj))
    return (adjusted_rand_index(y, pred), normalized_mutual_info(y, pred))


def bench_quality(fast: bool):
    reps = 2 if fast else 3
    datasets = {
        "blobs": (lambda s: blobs(n=2000, d=16, k=8, seed=s), 8, "gaussian"),
        "circles": (lambda s: circles(n=1500, seed=s), 2, "heat"),
        "moons": (lambda s: moons(n=1500, seed=s), 2, "heat"),
    }
    for dname, (gen, k, kname) in datasets.items():
        rows = {m: [] for m in ["full", "mb_beta", "mb_sklearn",
                                "trunc_beta", "nonkernel_mb"]}
        for s in range(reps):
            x, y = gen(s)
            if kname == "gaussian":
                kern, xj = GAUSS, jnp.asarray(x)
            else:
                kern, xi = heat_kernel(x, k=10, t=2000.0)
                kern = jax.tree.map(jnp.asarray, kern)
                xj = jnp.asarray(xi)
            t0 = time.perf_counter()
            a_fb, _ = fullbatch.fit(xj, kern, k, jax.random.PRNGKey(s),
                                    max_iters=30)
            t_fb = time.perf_counter() - t0
            rows["full"].append(
                (adjusted_rand_index(y, np.asarray(a_fb)), t_fb))
            for rate, row, keep_t in (("beta", "mb_beta", True),
                                      ("sklearn", "mb_sklearn", False)):
                # untruncated mini-batch == Algorithm 1 (DP) — NOT Alg2
                # with a giant window (whose O(k W^2) Gram would explode)
                cfg_u = MBConfig(k=k, batch_size=256, tau=0, rate=rate,
                                 max_iters=80, epsilon=-1.0)
                t0 = time.perf_counter()
                st_u, _ = untruncated.fit(xj, kern, cfg_u,
                                          jax.random.PRNGKey(s),
                                          early_stop=False)
                pred = np.asarray(untruncated.assignments(st_u, xj, kern))
                rows[row].append((adjusted_rand_index(y, pred),
                                  time.perf_counter() - t0 if keep_t
                                  else 0))
            t0 = time.perf_counter()
            ari, _ = _mb_fit_ari(xj, kern, k, 256, 200, "beta", y, s)
            rows["trunc_beta"].append((ari, time.perf_counter() - t0))
            _, assign, _ = lloyd.minibatch_kmeans_fit(
                jnp.asarray(x), k, jax.random.PRNGKey(s), batch_size=256,
                rate="beta", max_iters=80)
            rows["nonkernel_mb"].append(
                (adjusted_rand_index(y, np.asarray(assign)), 0))
        for m, vals in rows.items():
            aris = [v[0] for v in vals]
            ts = [v[1] for v in vals if v[1]]
            tstr = f"{np.mean(ts) * 1e6:.0f}" if ts else ""
            print(f"quality_{dname}_{m},{tstr},"
                  f"ARI={np.mean(aris):.3f}+-{np.std(aris):.3f}")


def bench_tau_sweep(fast: bool):
    x, y = circles(n=1500, seed=0)
    kern, xi = heat_kernel(x, k=10, t=2000.0)
    kern = jax.tree.map(jnp.asarray, kern)
    xj = jnp.asarray(xi)
    for tau in [50, 100, 200, 300]:
        t0 = time.perf_counter()
        ari, nmi = _mb_fit_ari(xj, kern, 2, 256, tau, "beta", y, 0)
        dt = (time.perf_counter() - t0) * 1e6
        print(f"tau_sweep_{tau},{dt:.0f},ARI={ari:.3f}")


def bench_rates(fast: bool):
    """beta vs sklearn, kernel AND non-kernel (fills Schwartzman'23 gap)."""
    x, y = blobs(n=2000, d=16, k=8, seed=1)
    xj = jnp.asarray(x)
    for rate in ["beta", "sklearn"]:
        ari, _ = _mb_fit_ari(xj, GAUSS, 8, 256, 200, rate, y, 0)
        print(f"rates_kernel_{rate},,ARI={ari:.3f}")
        objs = []
        for s in range(2):
            c, a, h = lloyd.minibatch_kmeans_fit(
                xj, 8, jax.random.PRNGKey(s), batch_size=256, rate=rate,
                max_iters=60)
            objs.append(adjusted_rand_index(y, np.asarray(a)))
        print(f"rates_nonkernel_{rate},,ARI={np.mean(objs):.3f}")


def bench_gamma_table(fast: bool):
    """Table 1 reproduction: gamma per dataset x kernel."""
    sets = {"circles": circles(n=1000, seed=0),
            "moons": moons(n=1000, seed=0),
            "blobs": blobs(n=1000, d=16, k=8, seed=0)}
    for dname, (x, _) in sets.items():
        print(f"gamma_{dname}_gaussian,,"
              f"{float(gamma_of(GAUSS, jnp.asarray(x))):.4f}")
        kk, xi = knn_kernel(x, k=10)
        g1 = float(gamma_of(jax.tree.map(jnp.asarray, kk), jnp.asarray(xi)))
        print(f"gamma_{dname}_knn,,{g1:.4f}")
        kh, xih = heat_kernel(x, k=10, t=2000.0)
        g2 = float(gamma_of(jax.tree.map(jnp.asarray, kh),
                            jnp.asarray(xih)))
        print(f"gamma_{dname}_heat,,{g2:.4f}")


def bench_termination(fast: bool):
    """Thm 1(2): iterations to early-stop scale ~ 1/epsilon (gamma = 1)."""
    from repro.api import KernelKMeans, SolverConfig

    x, _ = blobs(n=4000, d=16, k=8, seed=0)
    xj = jnp.asarray(x)
    for eps in [0.04, 0.02, 0.01, 0.005]:
        iters = []
        for s in range(2 if fast else 3):
            cfg = SolverConfig(k=8, batch_size=512, tau=200, epsilon=eps,
                               max_iters=400, kernel=GAUSS, cache="none",
                               distribution="single", jit=False)
            est = KernelKMeans(cfg).fit(xj, key=jax.random.PRNGKey(s))
            iters.append(len(est.history_))
        print(f"termination_eps{eps},,iters={np.mean(iters):.1f}")


# ------------------------------------------------------------ multi-restart
_MULTI_RESTART_SCRIPT = """
import time
import jax, jax.numpy as jnp, numpy as np
from repro.core import MBConfig, Gaussian, fit_jit
from repro.core.engine import MultiRestartEngine
from repro.data import blobs
from repro.launch.mesh import make_restart_mesh

R, REPS = {restarts}, {reps}
assert len(jax.devices()) == 8, jax.devices()
x, _ = blobs(n=4096, d=16, k=8, seed=0)
x = jnp.asarray(x)
kern = Gaussian(kappa=jnp.float32(1.0))
cfg = MBConfig(k=8, batch_size=128, tau=64, max_iters=25, epsilon=-1.0)
init_idx = jnp.arange(8, dtype=jnp.int32) * 100

# single restart via the repo's single-restart entry point (per-call cost,
# including the trace it pays on every invocation)
t0 = time.perf_counter()
_, it = fit_jit(x, kern, cfg, jax.random.PRNGKey(0), init_idx)
jax.block_until_ready(it)
t_single = time.perf_counter() - t0

mesh = make_restart_mesh(R)
eng = MultiRestartEngine(kern, cfg, restarts=R, mesh=mesh, init="random")
r = eng.fit(x, jax.random.PRNGKey(0))
jax.block_until_ready(r.objectives)          # one-time compile
t0 = time.perf_counter()
for _ in range(REPS):
    r = eng.fit(x, jax.random.PRNGKey(0))
    jax.block_until_ready(r.objectives)
t_multi = (time.perf_counter() - t0) / REPS

e1 = MultiRestartEngine(kern, cfg, restarts=1, init="random")
r1 = e1.fit(x, jax.random.PRNGKey(0))
jax.block_until_ready(r1.objectives)
t0 = time.perf_counter()
for _ in range(REPS):
    r1 = e1.fit(x, jax.random.PRNGKey(0))
    jax.block_until_ready(r1.objectives)
t_one = (time.perf_counter() - t0) / REPS

print(f"multi_restart_single_call,{{t_single * 1e6:.0f}},"
      f"one fit_jit restart per-call")
print(f"multi_restart_engine_R{{R}},{{t_multi * 1e6:.0f}},"
      f"{{t_multi / t_single:.2f}}x_vs_single_call "
      f"({{mesh.devices.size}}dev best-of-{{R}})")
print(f"multi_restart_amortized_R{{R}}_vs_R1,{{t_multi * 1e6:.0f}},"
      f"{{t_multi / t_one:.2f}}x_vs_compiled_R1")
"""


def _virtual_cpu_env(root: str) -> dict:
    """Environment of a bench child that runs on 8 virtual CPU devices.
    ``JAX_PLATFORMS=cpu`` keeps the child off the accelerator, which the
    parent process may already hold."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src")
    return env


def bench_multi_restart(fast: bool):
    """Engine claim: best-of-R fit in ONE compiled program is cheaper than
    2x a single restart as invoked today (fit_jit re-traces per call; the
    engine compiles once and vmaps the R fits).  Runs in a subprocess on 8
    virtual CPU devices so the restart axis really shards."""
    import os
    import subprocess
    import sys

    script = _MULTI_RESTART_SCRIPT.format(restarts=4, reps=2 if fast else 4)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", script],
                       env=_virtual_cpu_env(root), cwd=root,
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print(f"# multi_restart FAILED: {r.stderr[-500:]}")
        raise SystemExit(1)
    print("# multi_restart: CPU rehearsal on 8 virtual devices, "
          "not a device measurement")
    print(r.stdout, end="")


# ------------------------------------------------------------ fused restarts
_FUSED_RESTARTS_SCRIPT = """
import json, os, time
import jax, jax.numpy as jnp
from repro.api import KernelKMeans, SolverConfig
from repro.api import keys as api_keys
from repro.core import Gaussian
from repro.core.engine import make_init_run
from repro.data import blobs
from repro.launch.mesh import make_fused_mesh

R, REPS, ITERS = {restarts}, {reps}, {iters}
assert len(jax.devices()) == 8, jax.devices()
x, _ = blobs(n=4096, d=16, k=8, seed=0)
x = jnp.asarray(x)
kern = Gaussian(kappa=jnp.float32(1.0))
base = dict(k=8, batch_size=128, tau=64, max_iters=ITERS, epsilon=-1.0,
            kernel=kern, distribution="sharded", cache="none", jit=True)
key = jax.random.PRNGKey(0)

# both arms get the SAME precomputed (R, k) init indices, so the timed
# comparison is R fits (+ the fused plan's on-device winner selection,
# which is part of its deliverable) — not init-draw asymmetry
k_init, k_fit, k_eval = api_keys.restart_keys(key)
fit_keys = api_keys.per_restart(k_fit, R)
mb = SolverConfig(**base).mb_config()
init_idx = make_init_run(kern, mb, "kmeans++")(
    api_keys.per_restart(k_init, R), x)
jax.block_until_ready(init_idx)

# fused: R restarts x data x model in ONE compiled program
mesh = make_fused_mesh(R)
fused = KernelKMeans(SolverConfig(restarts=R, **base), mesh=mesh)
fused.fit(x, key, init_idx=init_idx)                 # compile
jax.block_until_ready(fused.result_.objectives)
assert fused.plan_.name == "fused_restart_sharded"

def best_of(fn, reps):
    # min over reps: robust to scheduler jitter on oversubscribed CI
    # hosts (8 virtual devices on ~2 cores), unlike a 2-rep mean
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)

def run_fused():
    fused.fit(x, key, init_idx=init_idx)
    jax.block_until_ready(fused.result_.objectives)

t_fused = best_of(run_fused, REPS)

# sequential baseline: the SAME R per-restart fits, one compiled sharded
# program per restart invoked back to back on all 8 devices (compiled
# program cached across calls — the fairest non-fused configuration)
mesh2 = jax.make_mesh((4, 2), ("data", "model"))
seq = KernelKMeans(SolverConfig(**base), mesh=mesh2)
ex = seq.plan_for(x.shape[0]).executor

def run_seq():
    for r in range(R):
        out = ex.fit(x, fit_keys[r], center_pts=x[init_idx[r]],
                     always_split=False)
        jax.block_until_ready(out.state.sqnorm)

run_seq()                                            # compile
t_seq = best_of(run_seq, REPS)

speedup = t_seq / t_fused
root = {root!r}
import sys
sys.path.insert(0, root)
from benchmarks.run import bench_env
out = dict(
    env=bench_env(seed=0),
    workload=dict(n=4096, d=16, k=8, batch_size=128, tau=64, iters=ITERS,
                  restarts=R, devices=8,
                  fused_mesh=list(mesh.devices.shape),
                  sequential_mesh=list(mesh2.devices.shape)),
    fused_ms=t_fused * 1e3, sequential_ms=t_seq * 1e3,
    speedup_x=speedup, plan="fused_restart_sharded",
    fused_faster=bool(t_fused < t_seq))
with open(os.path.join(root, "BENCH_fused_restarts.json"), "w") as f:
    json.dump(out, f, indent=2)
print(f"fused_restarts_sequential_R{{R}},{{t_seq * 1e6:.0f}},"
      f"R_sharded_fits_back_to_back")
print(f"fused_restarts_fused_R{{R}},{{t_fused * 1e6:.0f}},"
      f"{{speedup:.2f}}x_vs_sequential ({{mesh.devices.shape}} mesh)")
assert t_fused < t_seq, (
    f"fused {{t_fused * 1e3:.1f}}ms not faster than sequential "
    f"{{t_seq * 1e3:.1f}}ms")
"""


def bench_fused_restarts(fast: bool):
    """Tentpole claim: R restarts of the SHARDED step fused into one
    compiled program on a ("restart", "data", "model") mesh beat R
    back-to-back sharded fits (same per-restart keys, compiled programs
    cached in both arms).  Writes BENCH_fused_restarts.json; runs on 8
    virtual CPU devices in a subprocess so the restart axis really
    shards."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _FUSED_RESTARTS_SCRIPT.format(
        restarts=4, reps=2 if fast else 4, iters=15 if fast else 25,
        root=root)
    r = subprocess.run([sys.executable, "-c", script],
                       env=_virtual_cpu_env(root), cwd=root,
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print(f"# fused_restarts FAILED: {r.stderr[-500:]}")
        raise SystemExit(1)
    print("# fused_restarts: CPU rehearsal on 8 virtual devices, "
          "not a device measurement")
    print(r.stdout, end="")


# ------------------------------------------------------------ kernel cache
def bench_kernel_cache(fast: bool):
    """Gram tile cache (repro.cache): cached vs uncached fit + predict on a
    repeated-row workload.  Kernel-evaluation counts are MEASURED for the
    cached path (every miss = tile x n evals, from the cache counters) and
    analytic for the uncached path (per Algorithm-2 step: b*kW assignment +
    k*W^2 sqnorm recompute + b*kW direct eval; per predict query: kW).
    Writes machine-readable BENCH_kernel_cache.json at the repo root."""
    import json
    import os

    from repro.cache import predict_cached, stats
    from repro.core import fit, predict
    from repro.core.minibatch import fit_cached
    from repro.core.state import window_size as _wsz

    n = 2048 if fast else 4096
    d, k, b, tau = 16, 8, 256, 64
    iters = 10 if fast else 25
    tile = n // 16
    capacity = 16            # covers every row block: steady state = 0 miss
    reps = 4                 # 4x repeated-row query stream
    x, _ = blobs(n=n, d=d, k=k, seed=0)
    x = jnp.asarray(x)
    cfg = MBConfig(k=k, batch_size=b, tau=tau, max_iters=iters, epsilon=-1.0)
    init_idx = (jnp.arange(k, dtype=jnp.int32) * (n // k))
    kw = k * _wsz(b, tau)
    key = jax.random.PRNGKey(0)

    # --- uncached fit + predict --------------------------------------------
    t0 = time.perf_counter()
    st_u, hist_u = fit(x, GAUSS, cfg, key, init_idx=init_idx,
                       early_stop=False)
    jax.block_until_ready(st_u.sqnorm)
    t_fit_u = time.perf_counter() - t0
    evals_fit_u = len(hist_u) * (2 * b * kw + k * _wsz(b, tau) ** 2)

    qidx = jnp.tile(jnp.arange(n, dtype=jnp.int32), reps)
    xq = x[qidx]
    predict(st_u, x, xq, GAUSS).block_until_ready()   # warm compile
    t0 = time.perf_counter()
    pred_u = predict(st_u, x, xq, GAUSS)
    pred_u.block_until_ready()
    t_pred_u = time.perf_counter() - t0
    evals_pred_u = int(qidx.shape[0]) * kw

    # --- cached fit + predict (nested sampler raises the hit rate) ---------
    t0 = time.perf_counter()
    st_c, hist_c, ck = fit_cached(x, GAUSS, cfg, key, tile=tile,
                                  capacity=capacity, init_idx=init_idx,
                                  sampler="nested", early_stop=False)
    jax.block_until_ready(st_c.sqnorm)
    t_fit_c = time.perf_counter() - t0
    s_fit = stats(ck.cache)

    # warm compile WITHOUT threading the returned state, so the final
    # counters reflect the fit plus exactly ONE predict pass
    predict_cached(ck, st_c, qidx)[0].block_until_ready()
    t0 = time.perf_counter()
    pred_c, ck = predict_cached(ck, st_c, qidx)
    pred_c.block_until_ready()
    t_pred_c = time.perf_counter() - t0
    s_all = stats(ck.cache)

    evals_u = evals_fit_u + evals_pred_u
    evals_c = max(s_all["evals"], 1)
    reduction = evals_u / evals_c
    # The counters only see stateful (warm/insert) lookups; read-through
    # hits/misses inside the step are uncounted.  With capacity covering
    # every row block AND zero evictions, a block warmed once stays
    # resident forever, so every read-through access after its warm is a
    # hit — i.e. the measured miss count is the COMPLETE kernel-eval count.
    counters_complete = (s_all["evictions"] == 0
                         and capacity >= n // tile)
    assert counters_complete, (
        "eval accounting incomplete (evictions occurred); resize capacity")
    # numerical-equivalence check: same (cached-fit) state served through
    # the cache vs direct kernel evaluation — must agree exactly.  (pred_u
    # is a DIFFERENT fit — the uncached baseline uses the uniform sampler —
    # so it is only the timing/eval-count reference.)
    pred_ref = predict(st_c, x, xq, GAUSS)
    agree = float(jnp.mean((pred_ref == pred_c).astype(jnp.float32)))
    out = {
        "env": bench_env(seed=0),
        "workload": dict(n=n, d=d, k=k, batch_size=b, tau=tau, iters=iters,
                         tile=tile, capacity=capacity,
                         queries=int(qidx.shape[0]), sampler="nested",
                         fast=fast),
        "fit": dict(time_ms_uncached=t_fit_u * 1e3,
                    time_ms_cached=t_fit_c * 1e3,
                    evals_uncached=evals_fit_u, evals_cached=s_fit["evals"],
                    hits=s_fit["hits"], misses=s_fit["misses"],
                    evictions=s_fit["evictions"],
                    hit_rate=s_fit["hit_rate"]),
        "predict": dict(time_ms_uncached=t_pred_u * 1e3,
                        time_ms_cached=t_pred_c * 1e3,
                        evals_uncached=evals_pred_u,
                        label_agreement_same_state=agree),
        "totals": dict(evals_uncached=evals_u, evals_cached=evals_c,
                       eval_reduction_x=reduction,
                       hit_rate=s_all["hit_rate"],
                       counters_complete=counters_complete),
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_kernel_cache.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(f"kernel_cache_fit_uncached,{t_fit_u * 1e6:.0f},"
          f"{evals_fit_u}_evals")
    print(f"kernel_cache_fit_cached,{t_fit_c * 1e6:.0f},"
          f"{s_fit['evals']}_evals_hit_rate={s_fit['hit_rate']:.2f}")
    print(f"kernel_cache_predict_uncached,{t_pred_u * 1e6:.0f},"
          f"{evals_pred_u}_evals")
    print(f"kernel_cache_predict_cached,{t_pred_c * 1e6:.0f},"
          f"agreement={agree:.4f}")
    print(f"kernel_cache_reduction,,{reduction:.1f}x_fewer_kernel_evals")


# --------------------------------------------------------------- step fuse
def bench_step_fuse(fast: bool):
    """PR-5 tentpole gate: the streaming fused step (`step="fused"` —
    online-argmin assignment, slab-chunked sqnorm recompute, no
    materialized (b, k*W) strip) must beat the composed op chain on BOTH
    wall-clock and peak per-step temp memory (XLA compiled memory
    analysis), while staying bit-identical at f32.  Writes
    BENCH_step_fuse.json; asserted, so CI gates on it.

    The shape is assignment-dominated (k large, tau small relative to b):
    that is the regime the paper's O(k b (tau+b)) term governs and where
    the strip the fused step never materializes is the dominant
    intermediate."""
    import json
    import os

    from repro.core.minibatch import make_step
    from repro.core.state import init_state, window_size

    if fast:
        n, d, k, b, tau, reps = 4096, 32, 32, 512, 64, 3
    else:
        n, d, k, b, tau, reps = 8192, 64, 64, 1024, 64, 5
    x, _ = blobs(n=n, d=d, k=min(k, 16), seed=0)
    x = jnp.asarray(x)
    init_idx = (jnp.arange(k, dtype=jnp.int32) * 17) % n
    bidx = sample_batch(jax.random.PRNGKey(0), n, b)

    results = {}
    outs = {}
    for impl in ("composed", "fused"):
        cfg = MBConfig(k=k, batch_size=b, tau=tau, max_iters=5,
                       epsilon=-1.0, step=impl)
        st0 = init_state(x, init_idx, GAUSS, window_size(b, tau))
        step = jax.jit(make_step(GAUSS, cfg))
        temp_bytes = step.lower(st0, x, bidx).compile() \
            .memory_analysis().temp_size_in_bytes
        out = step(st0, x, bidx)
        jax.block_until_ready(out[0].sqnorm)        # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = step(st0, x, bidx)
            jax.block_until_ready(out[0].sqnorm)
            times.append(time.perf_counter() - t0)
        results[impl] = (min(times), temp_bytes)
        outs[impl] = out
        print(f"step_fuse_{impl},{min(times) * 1e6:.0f},"
              f"temp_{temp_bytes / 1e6:.0f}MB")

    bit_identical = bool(
        np.array_equal(np.asarray(outs["composed"][0].sqnorm),
                       np.asarray(outs["fused"][0].sqnorm))
        and np.array_equal(np.asarray(outs["composed"][0].idx),
                           np.asarray(outs["fused"][0].idx))
        and np.array_equal(np.asarray(outs["composed"][1].improvement),
                           np.asarray(outs["fused"][1].improvement)))
    t_c, m_c = results["composed"]
    t_f, m_f = results["fused"]
    out = dict(
        env=bench_env(seed=0),
        workload=dict(n=n, d=d, k=k, batch_size=b, tau=tau,
                      window=tau + b, reps=reps, fast=fast,
                      backend=jax.default_backend()),
        composed=dict(step_ms=t_c * 1e3, temp_bytes=m_c),
        fused=dict(step_ms=t_f * 1e3, temp_bytes=m_f),
        speedup_x=t_c / t_f, temp_reduction_x=m_c / max(m_f, 1),
        bit_identical=bit_identical,
        fused_faster=bool(t_f < t_c),
        fused_smaller=bool(m_f < m_c))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_step_fuse.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(f"step_fuse_speedup,,{t_c / t_f:.2f}x_wall_clock")
    print(f"step_fuse_temp_reduction,,{m_c / max(m_f, 1):.2f}x_peak_temp")
    assert bit_identical, "fused step diverged from composed at f32"
    assert t_f < t_c, (f"fused {t_f * 1e3:.0f}ms not faster than "
                       f"composed {t_c * 1e3:.0f}ms")
    assert m_f < m_c, (f"fused temp {m_f} not below composed {m_c}")


# ------------------------------------------------------------- api overhead
def bench_api_overhead(fast: bool):
    """Estimator-vs-direct parity: KernelKMeans dispatch must resolve at
    trace time, so a repeat `fit` through the estimator (compiled program
    cached on the executor) costs the same as invoking a hand-built jitted
    while_loop — zero per-step Python overhead.  Also reports the legacy
    fit_jit per-call cost (which re-traces every invocation) for contrast.
    """
    import warnings

    from repro.api import KernelKMeans, SolverConfig
    from repro.core.minibatch import (
        make_step, run_early_stopped, sampled_step_with_key)
    from repro.core.state import init_state, window_size

    n = 2048 if fast else 4096
    k, b, tau, d = 8, 128, 64, 16
    iters, reps = 25, 3 if fast else 6
    x, _ = blobs(n=n, d=d, k=k, seed=0)
    x = jnp.asarray(x)
    mb = MBConfig(k=k, batch_size=b, tau=tau, max_iters=iters, epsilon=-1.0)
    init_idx = jnp.arange(k, dtype=jnp.int32) * (n // k)
    key = jax.random.PRNGKey(0)

    # direct baseline: hand-built compiled loop, traced once
    w = window_size(b, tau)
    step = make_step(GAUSS, mb)

    @jax.jit
    def direct(x, init_idx, key):
        state0 = init_state(x, init_idx, GAUSS, w)
        return run_early_stopped(mb, sampled_step_with_key(step, x, mb),
                                 state0, key)

    jax.block_until_ready(direct(x, init_idx, key)[0].sqnorm)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(direct(x, init_idx, key)[0].sqnorm)
    t_direct = (time.perf_counter() - t0) / reps

    # estimator: same plan point, compiled program cached on the executor
    from repro.api.executors import program_builds

    est = KernelKMeans(SolverConfig(
        k=k, batch_size=b, tau=tau, max_iters=iters, epsilon=-1.0,
        kernel=GAUSS, cache="none", distribution="single", jit=True))
    est.fit(x, key, init_idx=init_idx)                        # compile
    jax.block_until_ready(est.state_.sqnorm)
    builds_before = program_builds()
    t0 = time.perf_counter()
    for _ in range(reps):
        est.fit(x, key, init_idx=init_idx)
        jax.block_until_ready(est.state_.sqnorm)
    t_est = (time.perf_counter() - t0) / reps
    rebuilds = program_builds() - builds_before

    # legacy fit_jit: pays a re-trace on every call (the cost the
    # estimator's cached executor removes)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core import fit_jit
        jax.block_until_ready(
            fit_jit(x, GAUSS, mb, key, init_idx)[0].sqnorm)
        t0 = time.perf_counter()
        jax.block_until_ready(
            fit_jit(x, GAUSS, mb, key, init_idx)[0].sqnorm)
        t_legacy = time.perf_counter() - t0

    ratio = t_est / t_direct
    print(f"api_overhead_direct,{t_direct * 1e6:.0f},compiled_loop")
    print(f"api_overhead_estimator,{t_est * 1e6:.0f},"
          f"{ratio:.2f}x_vs_direct")
    print(f"api_overhead_repeat_builds,{rebuilds},programs_rebuilt")
    print(f"api_overhead_legacy_fit_jit,{t_legacy * 1e6:.0f},"
          f"{t_legacy / t_direct:.2f}x_vs_direct (per-call retrace)")
    assert ratio < 1.5, (
        f"estimator dispatch overhead {ratio:.2f}x vs direct compiled "
        "call — plan dispatch must resolve at trace time")
    assert rebuilds == 0, (
        f"{rebuilds} compiled programs rebuilt across {reps} repeat fits "
        "— the loop-core program cache must hold them flat (the PR-5 "
        "contract, re-pinned after the PR-9 loop-core refactor)")


# ----------------------------------------------------------------- service
def bench_service(fast: bool):
    """PR-7 serving gate (docs/serving.md): the learner/actor split must
    serve microbatched ``predict`` at p99 <= 2x a bare ``predict`` call at
    the same bucket shape, with ZERO recompiles after warmup (both the
    cross-executor ``program_builds()`` counter and the actor's own
    ``serve_compiles``), and keep serving across atomic snapshot swaps
    with the load+warm pause bounded and reported.  Writes
    BENCH_service.json; asserted, so CI gates on it.

    Three phases: (1) learner rounds — the resume program must compile
    once and stay flat; (2) steady-state closed-loop serving — latency vs
    the bare baseline; (3) snapshot churn — a publisher thread pushes new
    versions while the closed loop keeps serving, exercising the
    off-serving-path swap."""
    import json
    import os
    import tempfile
    import threading

    from repro.api.executors import program_builds
    from repro.service.demo import build_service
    from repro.service.telemetry import LatencyWindow

    if fast:
        capacity, b, tau, k, d = 1024, 128, 64, 8, 16
        bucket, rounds, reps_bare, warm_reqs, measured = 256, 4, 40, 8, 80
        n_swaps = 2
    else:
        capacity, b, tau, k, d = 2048, 256, 128, 8, 16
        bucket, rounds, reps_bare, warm_reqs, measured = 512, 6, 60, 16, 250
        n_swaps = 3

    with tempfile.TemporaryDirectory(prefix="repro_bench_svc_") as snapdir:
        learner, actor, store, buf, _ = build_service(
            snapdir, k=k, d=d, capacity=capacity, batch_size=b, tau=tau,
            iters_per_round=2, publish_every=2, buckets=(bucket,),
            queue_depth=64, max_wait_ms=0.5)
        actor.poll_every_s = 0.05           # snappy swap pickup

        # phase 1: learner rounds; the partial_fit resume program must
        # compile on round 1 and never again (fixed buffer shape)
        builds_per_round = []
        learner.on_round = lambda r: builds_per_round.append(
            program_builds())
        learner.run(rounds)
        assert builds_per_round[-1] == builds_per_round[1], (
            f"resume program rebuilt across rounds: {builds_per_round}")
        print(f"service_fit_builds,,"
              f"{builds_per_round[-1]}_flat_after_round_1")

        # bare baseline: the same assignment at the same (bucket, d)
        # shape, no queue/pad/thread in the way
        _, est_bare = store.load()
        rng = np.random.default_rng(123)
        queries = [rng.normal(0, 1, (bucket, d)).astype(np.float32)
                   for _ in range(8)]
        np.asarray(est_bare.predict(queries[0]))          # compile + warm
        bare = []
        for i in range(reps_bare):
            t0 = time.perf_counter()
            np.asarray(est_bare.predict(queries[i % len(queries)]))
            bare.append((time.perf_counter() - t0) * 1e3)
        bare_p50, bare_p99 = (float(np.percentile(bare, q))
                              for q in (50, 99))

        # actor warmup, then freeze the compile counters
        actor.start()
        for i in range(warm_reqs):
            actor.predict(queries[i % len(queries)])
        builds_warm = program_builds()
        serve_warm = actor.serve_compiles

        # phase 2: steady-state closed loop — full-bucket requests, so no
        # coalesce wait and no padding; latency is queue + serve + scatter
        actor.latency = LatencyWindow()
        t0 = time.perf_counter()
        for i in range(measured):
            actor.predict(queries[i % len(queries)])
        wall = time.perf_counter() - t0
        micro = actor.latency.percentiles()
        qps_rows = measured * bucket / wall

        # phase 3: snapshot churn while serving — the swapper thread
        # loads + warms off the serving path; the closed loop must keep
        # completing requests throughout
        base_v = store.latest_version()

        def _publish():
            for j in range(n_swaps):
                time.sleep(0.25)
                store.publish(learner.est, base_v + j + 1)

        swaps_before = actor.swaps
        actor.latency = LatencyWindow()
        pub = threading.Thread(target=_publish, daemon=True)
        pub.start()
        served_churn = 0
        t0 = time.perf_counter()
        while (actor.swaps - swaps_before < n_swaps
               and time.perf_counter() - t0 < 30.0):
            actor.predict(queries[served_churn % len(queries)])
            served_churn += 1
        pub.join(10.0)
        churn = actor.latency.percentiles()
        swaps_during = actor.swaps - swaps_before
        pause_ms = actor.last_swap_pause_ms
        builds_end = program_builds()
        serve_end = actor.serve_compiles
        actor.stop()

    ratio = micro["p99"] / bare_p99
    print(f"service_bare_predict,{bare_p50 * 1e3:.0f},"
          f"p99={bare_p99:.2f}ms")
    print(f"service_microbatch,{micro['p50'] * 1e3:.0f},"
          f"p99={micro['p99']:.2f}ms {ratio:.2f}x_bare "
          f"{qps_rows:.0f}rows_per_s")
    print(f"service_swap,,{swaps_during}_swaps "
          f"pause={pause_ms:.0f}ms served_during={served_churn}")

    out = dict(
        env=bench_env(seed=0),
        workload=dict(k=k, d=d, capacity=capacity, batch_size=b, tau=tau,
                      bucket=bucket, rounds=rounds, fast=fast,
                      backend=jax.default_backend()),
        fit_builds_per_round=builds_per_round,
        bare_ms=dict(p50=bare_p50, p99=bare_p99, reps=reps_bare),
        micro_ms=dict(p50=micro["p50"], p99=micro["p99"],
                      count=micro["count"]),
        micro_over_bare_p99=ratio,
        qps_rows=qps_rows,
        qps_requests=measured / wall,
        swap=dict(swaps=swaps_during, last_pause_ms=pause_ms,
                  served_during_churn=served_churn,
                  p99_during_churn_ms=churn["p99"]),
        programs=dict(fit_builds=builds_end, serve_compiles=serve_end,
                      recompiles_after_warmup=(builds_end - builds_warm)
                      + (serve_end - serve_warm)))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_service.json"), "w") as f:
        json.dump(out, f, indent=2)

    assert ratio <= 2.0, (
        f"microbatched p99 {micro['p99']:.2f}ms is {ratio:.2f}x the bare "
        f"predict p99 {bare_p99:.2f}ms at the same ({bucket}, {d}) shape")
    assert builds_end == builds_warm and serve_end == serve_warm, (
        f"recompiles after warmup: fit {builds_warm}->{builds_end}, "
        f"serve {serve_warm}->{serve_end}")
    assert swaps_during >= 1, "no snapshot swap observed while serving"
    assert pause_ms is not None and pause_ms < 10_000, (
        f"snapshot swap load+warm took {pause_ms}ms")
    assert served_churn > 0, "serving stalled during snapshot churn"


# --------------------------------------------------------------- landmark
def bench_landmark(fast: bool):
    """Landmark-compression gate (docs/compression.md): on an unbounded
    stream (the ``grow_window`` no-eviction baseline, support never
    truncated) serving cost grows linearly with fit history, while
    round-cadence Nystrom compression pins it at O(k*m) — predict latency
    must stay flat (<= 1.1x round 1) as the uncompressed arm's grows, and
    the compressed objective on a held-out eval batch must stay within 5%
    of the uncompressed run's.  Writes BENCH_landmark.json; asserted, so
    CI gates on it.

    Both arms run the SAME batch schedule from the SAME init; the only
    difference is what happens between rounds: grow the window (baseline)
    vs project onto m landmarks (compressed)."""
    import json
    import os

    from repro.core.minibatch import assign_chunked, center_distances_chunked
    from repro.landmark import CompressSpec, compress_state, grow_window

    if fast:
        n, d, k, b, tau = 8192, 16, 8, 128, 64
        rounds, iters, m, grow, reps, nq = 10, 6, 32, 96, 8, 2048
    else:
        n, d, k, b, tau = 16384, 32, 16, 256, 128
        rounds, iters, m, grow, reps, nq = 12, 8, 64, 192, 10, 4096

    x, _ = blobs(n=n, d=d, k=k, seed=0)
    x = jnp.asarray(x)
    xe, _ = blobs(n=nq, d=d, k=k, seed=1)          # held-out eval batch
    xe = jnp.asarray(xe)
    w0 = window_size(b, tau)
    init_idx = (jnp.arange(k, dtype=jnp.int32) * 31) % n
    cfg = MBConfig(k=k, batch_size=b, tau=tau, max_iters=iters,
                   epsilon=-1.0)
    spec = CompressSpec(every=0, m=m)
    key = jax.random.PRNGKey(42)
    assign = jax.jit(assign_chunked, static_argnames=("chunk",))
    dists = jax.jit(center_distances_chunked, static_argnames=("chunk",))

    def run_round(st, rnd):
        # both arms share this schedule; the step program is rebuilt per
        # window width in the grown arm (learner-side cost, not timed)
        step = jax.jit(make_step(GAUSS, cfg))
        for i in range(iters):
            bidx = sample_batch(jax.random.fold_in(key, rnd * iters + i),
                                n, b)
            st, _ = step(st, x, bidx)
        return st

    def time_rounds(servings):
        """Per-round best-of-``reps`` predict latency (ms).  Reps are
        INTERLEAVED round-robin across rounds so slow machine periods hit
        every round equally — the per-round minima then reflect shape
        cost, not when in the run a round happened to be timed."""
        for coef, sqnorm, sup in servings:          # compile + warm all
            jax.block_until_ready(assign(GAUSS, coef, sqnorm, sup, xe,
                                         4096))
        times = [[] for _ in servings]
        for _ in range(reps):
            for i, (coef, sqnorm, sup) in enumerate(servings):
                t0 = time.perf_counter()
                jax.block_until_ready(assign(GAUSS, coef, sqnorm, sup,
                                             xe, 4096))
                times[i].append(time.perf_counter() - t0)
        return [min(t) * 1e3 for t in times]

    def objective(coef, sqnorm, sup):
        dd = dists(GAUSS, coef, sqnorm, sup, xe, 4096)
        return float(jnp.mean(jnp.min(dd, axis=1)))

    # ---- uncompressed arm: fit, then widen the window every round
    st_u = init_state(x, init_idx, GAUSS, w0)
    servings_u, rows_u = [], []
    for rnd in range(rounds):
        st_u = run_round(st_u, rnd)
        sup = x[st_u.idx.reshape(-1)]
        servings_u.append((st_u.coef, st_u.sqnorm, sup))
        rows_u.append(int(sup.shape[0]))
        if rnd < rounds - 1:
            st_u = grow_window(st_u, grow)
    obj_u = objective(*servings_u[-1])

    # ---- compressed arm: same schedule at fixed W, project onto m
    # landmarks every round and serve the O(k*m) representation
    st_c = init_state(x, init_idx, GAUSS, w0)
    servings_c, drifts = [], []
    for rnd in range(rounds):
        st_c = run_round(st_c, rnd)
        st_c, info = compress_state(GAUSS, st_c, spec, x=x)
        jax.block_until_ready(st_c.coef)
        drifts.append(float(info.drift_bound))
        # after compression only the first m slots are live — that slice
        # IS the CompressedKernelCenters serving tuple
        servings_c.append((st_c.coef[:, :m], st_c.sqnorm,
                           x[st_c.idx[:, :m].reshape(-1)]))
    obj_c = objective(*servings_c[-1])

    lat_u = time_rounds(servings_u)
    lat_c = time_rounds(servings_c)

    growth_u = lat_u[-1] / lat_u[0]
    growth_c = lat_c[-1] / lat_c[0]
    obj_gap = abs(obj_c - obj_u) / max(abs(obj_u), 1e-12)
    print(f"landmark_uncompressed,{lat_u[-1] * 1e3:.0f},"
          f"{growth_u:.2f}x_round1 rows={rows_u[0]}->{rows_u[-1]}")
    print(f"landmark_compressed,{lat_c[-1] * 1e3:.0f},"
          f"{growth_c:.2f}x_round1 rows={k * m} m={m}")
    print(f"landmark_objective,,gap={obj_gap:.4f} "
          f"drift_bound={max(drifts):.3f}")

    out = dict(
        env=bench_env(seed=42),
        workload=dict(n=n, d=d, k=k, batch_size=b, tau=tau, window=w0,
                      rounds=rounds, iters_per_round=iters, m=m,
                      grow_per_round=grow, eval_rows=nq, reps=reps,
                      fast=fast),
        uncompressed=dict(predict_ms=lat_u, support_rows=rows_u,
                          latency_growth_x=growth_u, objective=obj_u),
        compressed=dict(predict_ms=lat_c, support_rows=k * m,
                        latency_growth_x=growth_c, objective=obj_c,
                        drift_bounds=drifts),
        objective_gap=obj_gap,
        compression_ratio=m / (w0 + (rounds - 1) * grow))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_landmark.json"), "w") as f:
        json.dump(out, f, indent=2)

    assert growth_c <= 1.1, (
        f"compressed predict latency grew {growth_c:.2f}x over "
        f"{rounds} rounds (must stay flat <= 1.1x round 1)")
    assert growth_u > 1.1, (
        f"uncompressed baseline only grew {growth_u:.2f}x — the no-"
        f"eviction arm is not exercising unbounded support growth")
    assert obj_gap <= 0.05, (
        f"compressed objective {obj_c:.4f} deviates {obj_gap:.1%} from "
        f"uncompressed {obj_u:.4f} on the held-out batch (> 5%)")


# ------------------------------------------------------------------ chaos
def bench_chaos(fast: bool):
    """PR-10 robustness gate (docs/robustness.md): the service under a
    deterministic injected fault schedule must (1) recover a learner
    carry BIT-IDENTICAL to the fault-free run — crashes, hung steps and
    a corrupt checkpoint included, (2) reproduce the exact same fault
    trace when the same plan seed is run twice, (3) lose ZERO admitted
    requests and never swap a corrupt snapshot in during an actor soak
    with corrupt publishes + transient swap/serve IOErrors, with p99
    bounded throughout.  Writes BENCH_chaos.json; asserted, so CI gates
    on it."""
    import json
    import os
    import tempfile

    from repro.service import FaultPlan, FaultRule
    from repro.service.demo import build_service

    if fast:
        k, d, capacity, b, tau = 4, 8, 128, 32, 16
        rounds, soak_reqs, bucket = 8, 40, 64
    else:
        k, d, capacity, b, tau = 4, 8, 256, 32, 16
        rounds, soak_reqs, bucket = 10, 80, 64

    svc_kw = dict(k=k, d=d, capacity=capacity, batch_size=b, tau=tau,
                  iters_per_round=2, arrivals_per_step=64,
                  buckets=(bucket,), publish_every=2)

    def leaves(carry):
        return [np.asarray(x) for x in jax.tree.leaves(carry)]

    # ---- phase 1: fault-free reference carry
    with tempfile.TemporaryDirectory(prefix="repro_chaos_ref_") as sd:
        l_ref, *_ = build_service(sd, **svc_kw)
        carry_ref = l_ref.run(rounds)

    # ---- phase 2: crash + hung step + corrupt checkpoint, twice.
    # The schedule: the 2nd publish is byte-corrupted on disk, a crash
    # hits step 5 (so the restore must FALL BACK past the corrupt v4 to
    # v2), and a 120s hang hits a later step (so only the WATCHDOG can
    # save the run).  Recovery must converge bit-identically, and the
    # same seed must fire the same trace both times.
    def chaos_run(sd):
        plan = FaultPlan([
            FaultRule("snapshot.publish", "corrupt", at=(1,)),
            FaultRule("learner.step", "crash", at=(5,)),
            FaultRule("learner.step", "hang", at=(9,), delay_s=120.0),
        ], seed=42)
        l, _, store, *_ = build_service(sd, faults=plan,
                                        step_timeout_s=10.0, **svc_kw)
        carry = l.run(rounds, max_restarts=5)
        return carry, plan.trace_list(), l.stats(), store

    with tempfile.TemporaryDirectory(prefix="repro_chaos_a_") as sd:
        carry_a, trace_a, stats_a, store_a = chaos_run(sd)
        quarantined_a = store_a.quarantined
    with tempfile.TemporaryDirectory(prefix="repro_chaos_b_") as sd:
        carry_b, trace_b, _, _ = chaos_run(sd)

    bit_identical = all(
        np.array_equal(x, y) for x, y in zip(leaves(carry_ref),
                                             leaves(carry_a)))
    replayed = all(
        np.array_equal(x, y) for x, y in zip(leaves(carry_a),
                                             leaves(carry_b)))
    print(f"chaos_recovery,,bit_identical={bit_identical} "
          f"watchdog={stats_a['watchdog_fires']} "
          f"fallbacks={stats_a['restore_fallbacks']} "
          f"restores={stats_a['restores']}")
    print(f"chaos_replay,,trace_len={len(trace_a)} "
          f"identical={trace_a == trace_b}")

    # ---- phase 3: actor soak under corrupt publishes + transient
    # swap/serve IOErrors.  `at`-indexed transients guarantee the retry
    # (occurrence+1) succeeds, so every admitted request must complete.
    soak_plan = FaultPlan([
        FaultRule("snapshot.publish", "corrupt", every=3, max_fires=2),
        FaultRule("actor.swap", "io", at=(1,)),
        FaultRule("actor.serve", "io", at=(2, 7, 13)),
    ], seed=7)
    lost = served = 0
    with tempfile.TemporaryDirectory(prefix="repro_chaos_soak_") as sd:
        soak_kw = dict(svc_kw, publish_every=1)
        l, actor, store, buf, _ = build_service(sd, faults=soak_plan,
                                                **soak_kw)
        actor.poll_every_s = 0.05
        actor.serve_retries = 2
        l.run(2)                        # first snapshots exist
        l.start(rounds)                 # keep publishing (some corrupt)
        actor.start()
        rng = np.random.default_rng(123)
        queries = [rng.normal(0, 1, (bucket, d)).astype(np.float32)
                   for _ in range(8)]
        pending = []
        for i in range(soak_reqs):
            pending.append(actor.submit(queries[i % len(queries)]))
            if len(pending) >= 8:
                for req in pending:
                    try:
                        req.wait(60.0)
                        served += 1
                    except Exception:   # noqa: BLE001 — counted as lost
                        lost += 1
                pending.clear()
        for req in pending:
            try:
                req.wait(60.0)
                served += 1
            except Exception:           # noqa: BLE001
                lost += 1
        l.join(120.0)
        actor.stop()
        l.stop()
        # the injected corrupt publishes may have been SKIPPED rather
        # than quarantined (a newer intact version can land before the
        # actor polls — also correct).  Force the deterministic case:
        # corrupt the newest snapshot on disk, then swap — the actor
        # must quarantine it and acquire the newest INTACT version.
        newest = store.latest_version()
        with open(store.path_for(newest), "r+b") as f:
            f.seek(64)
            byte = f.read(1)
            f.seek(64)
            f.write(bytes([byte[0] ^ 0xFF]))
        actor.try_swap(force=True)
        final_version = actor.version
        intact = store.versions()
        lat = actor.latency.percentiles()
        q_stats = actor.queue_stats()
        snap_stats = actor.snapshot_stats()
        quarantined_soak = store.quarantined

    print(f"chaos_soak,,served={served}/{soak_reqs} lost={lost} "
          f"quarantined={quarantined_soak} "
          f"swap_failures={snap_stats['swap_failures']} "
          f"p99={lat['p99']:.1f}ms")

    out = dict(
        env=bench_env(seed=0),
        workload=dict(k=k, d=d, capacity=capacity, batch_size=b, tau=tau,
                      rounds=rounds, soak_reqs=soak_reqs, fast=fast,
                      backend=jax.default_backend()),
        recovery=dict(bit_identical_to_fault_free=bit_identical,
                      watchdog_fires=stats_a["watchdog_fires"],
                      restore_fallbacks=stats_a["restore_fallbacks"],
                      restores=stats_a["restores"],
                      quarantined=quarantined_a),
        replay=dict(trace=trace_a, identical=trace_a == trace_b),
        soak=dict(admitted=soak_reqs, served=served, lost=lost,
                  quarantined=quarantined_soak,
                  swap_failures=snap_stats["swap_failures"],
                  serve_retried=q_stats["serve_retried"],
                  final_version=final_version,
                  intact_versions=intact,
                  p50_ms=lat["p50"], p99_ms=lat["p99"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_chaos.json"), "w") as f:
        json.dump(out, f, indent=2)

    assert bit_identical, (
        "recovered carry differs from the fault-free run under the "
        "injected schedule")
    assert replayed and trace_a == trace_b, (
        f"same seed did not reproduce the same run: trace_a={trace_a} "
        f"trace_b={trace_b}")
    assert stats_a["watchdog_fires"] >= 1, "hung step never detected"
    assert stats_a["restore_fallbacks"] >= 1, (
        "corrupt checkpoint never forced a restore fallback")
    assert lost == 0, f"{lost} admitted requests lost during the soak"
    assert quarantined_soak >= 1, "no corrupt publish was quarantined"
    assert final_version in intact, (
        f"served version {final_version} is not an intact snapshot")
    assert lat["p99"] is not None and lat["p99"] < 5_000.0, (
        f"p99 {lat['p99']:.0f}ms unbounded during recovery")


BENCHES = {
    "speedup": bench_speedup,
    "multi_restart": bench_multi_restart,
    "fused_restarts": bench_fused_restarts,
    "kernel_cache": bench_kernel_cache,
    "step_fuse": bench_step_fuse,
    "api_overhead": bench_api_overhead,
    "service": bench_service,
    "chaos": bench_chaos,
    "landmark": bench_landmark,
    "n_independence": bench_n_independence,
    "quality": bench_quality,
    "tau_sweep": bench_tau_sweep,
    "rates": bench_rates,
    "gamma_table": bench_gamma_table,
    "termination": bench_termination,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---")
        t0 = time.time()
        fn(args.fast)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
