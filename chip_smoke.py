#!/usr/bin/env python3
"""Smoke run of the mini-batch kernel k-means main path on one TPU chip.

    python chip_smoke.py                # one chip: fit, predict, service
    python chip_smoke.py --four-chips   # four chips: the sharded plans

One chip, through the entry points a user calls, at the widths of
``repro/configs/paper_cluster.py`` (k=256 centers, d=1024, Gaussian kernel
with kappa=2.0) on n=2**18 points generated from ``--seed``:

* ``KernelKMeans(SolverConfig(...)).fit`` with the default ``step="auto"``,
  which on TPU is the fused step running the Pallas streaming kernel,
  to the paper's early stop (``epsilon`` and ``max_iters`` of the config);
* ``predict`` on 65,536 queries;
* a few rounds of the learner/actor service (``service.demo.run_demo``) at
  the same k and d.

It checks what it ran: the device is a TPU, the compiled fit step holds a
Pallas kernel (``tpu_custom_call``), the Pallas assignment agrees with the
XLA reference on one batch of the fitted state, predict agrees with a
plain reference on a slice of the queries, and the service served every
admitted request with no failed swap and no recompile after warm-up.  Any
failed check exits non-zero.  The last line of standard output is one JSON
object naming the device; earlier lines report sizes, cuts and the
compile and steady seconds of every phase.

``--four-chips`` runs only the sharded path and what it is compared with:
``distribution="sharded"`` fits (on-device loop and host batch stream) and
predicts on a 4-device data mesh, a ``fused_restart_sharded`` fit with
R=4, and the one-device fits (R=1 and R=4) from the same seed.

Everything runs in this one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))

# Pallas-vs-XLA agreement on one fitted batch.  Pallas lowers a
# default-precision dot to Mosaic with no contract-precision attribute
# (fp32 only when the dot asks for HIGHEST), and the kernel does not ask.
# Its f32 path sat 6.0e-4 from the "highest" (full f32) reference on TPU
# v5e, the size of a one-bf16-pass error: the kernel's own bf16 path
# (``bf16=True``, run here as the control on the same batch) sat 4.3e-4
# away in interpret mode at d=1024, k=32, W=512.  So no limit against
# "highest" separates the two paths; BEST_DIST_ATOL admits one bf16 pass
# with room (about 3x the f32 reading) and fails a kernel that rounds
# coarser, such as one accumulating in bf16.  Labels may differ only
# where two centers are that close: at most 1% of the rows.
BEST_DIST_ATOL = 2e-3
MIN_LABEL_AGREEMENT = 0.99
# Each predicted cluster, matched to the blob most of its queries came
# from, must hold at least this share of the queries.  A fit that merged
# many blobs into a few centers (or served garbage) falls far below it;
# 256 centers seeded by k-means++ on 256 blobs leave some blobs split or
# merged, so the bound is not near 1.
MIN_BLOB_AGREEMENT = 0.5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke run allocates.  Widths (k, d, kappa) are
    paper_cluster's own; n, b=tau and the predict chunk are cut as one
    chip and the run's time limit force (``cuts()`` says why)."""

    d: int
    k: int
    kappa: float
    n: int = 2 ** 18
    batch: int = 2048           # b = tau
    queries: int = 65536
    chunk: int = 512            # predict rows per (chunk, k*W) strip
    check_rows: int = 512       # queries checked against the reference
    service_batch: int = 256
    service_tau: int = 128
    service_rounds: int = 4
    service_capacity: int = 2048
    requests: int = 48
    request_rows: int = 256
    max_iters: int = 0          # 0: paper_cluster's own max_iters

    @property
    def window(self) -> int:
        return 2 * self.batch

    def spread(self) -> float:
        # blobs() draws each point as a unit-norm center plus spread * N(0,
        # I_d).  Two points of one blob are 2 * spread**2 * d apart in
        # squared distance; this makes that kappa / 4 (kernel value
        # e^-0.25), against about 2 + kappa / 4 between blobs (e^-1.25).
        # blobs' default spread 0.15 puts points of one blob 46 apart at
        # d=1024, where every kernel value is ~1e-10, below the f32
        # resolution of a feature-space distance; at kappa apart (e^-0.5
        # against e^-1.5) the fit collapses 256 centers onto a handful.
        return math.sqrt(self.kappa / (8 * self.d))


def paper_sizes() -> Sizes:
    from repro.configs import paper_cluster

    return Sizes(d=paper_cluster.EMBED_DIM, k=paper_cluster.CONFIG.k,
                 kappa=paper_cluster.KAPPA)


def cuts(s: Sizes) -> list:
    from repro.configs import paper_cluster

    cfg = paper_cluster.CONFIG
    gib = 2 ** 30

    def support_gib(b, tau):
        return s.k * (b + tau) * s.d * 4 / gib

    return [
        f"b=tau {cfg.batch_size}->{s.batch}: the fused step gathers the "
        f"(k*W, d) f32 support, {support_gib(cfg.batch_size, cfg.tau):.0f}"
        f" GiB at b=tau={cfg.batch_size}, the whole HBM; predict holds the "
        f"gathered support and its copies, "
        f"{support_gib(2 * s.batch, 2 * s.batch):.0f} GiB each at "
        f"b=tau={2 * s.batch}; {support_gib(s.batch, s.batch):.0f} GiB "
        f"at b=tau={s.batch}",
        f"predict chunk 4096->{s.chunk}: the (chunk, k*W) f32 strip is "
        f"{s.chunk * s.k * s.window * 4 / gib:.0f} GiB",
        f"service b={s.service_batch} tau={s.service_tau}: every snapshot "
        f"writes its {support_gib(s.service_batch, s.service_tau):.2f} GiB"
        " support to disk",
    ]


# ------------------------------------------------------------ timing
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache), summed over every
    thread, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def timed(clock: CompileClock, fn):
    """-> (fn(), {wall_s, compile_s, steady_s}); ``fn`` must block until
    its device work is done."""
    c0 = clock.seconds
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    return out, {"wall_s": round(wall, 3), "compile_s": round(comp, 3),
                 "steady_s": round(max(wall - comp, 0.0), 3)}


def report(name: str, **fields) -> None:
    print(f"# {name}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------ phases
def make_data(s: Sizes, seed: int):
    """(x (n, d), xq (queries, d)) on the default device, and the queries'
    blob ids (numpy), from ``seed``."""
    import jax.numpy as jnp

    from repro.data import blobs

    pts, blob = blobs(n=s.n + s.queries, d=s.d, k=s.k, spread=s.spread(),
                      seed=seed)
    x = jnp.asarray(pts[:s.n])
    xq = jnp.asarray(pts[s.n:])
    x.block_until_ready()
    xq.block_until_ready()
    return x, xq, blob[s.n:]


def solver_config(s: Sizes, **axes):
    from repro.api import SolverConfig
    from repro.configs import paper_cluster

    cfg = paper_cluster.CONFIG
    return SolverConfig(
        k=s.k, batch_size=s.batch, tau=s.batch, rate=cfg.rate,
        sqnorm_mode=cfg.sqnorm_mode, eval_mode=cfg.eval_mode,
        epsilon=cfg.epsilon, max_iters=s.max_iters or cfg.max_iters,
        kernel="rbf",
        kernel_params={"kappa": s.kappa}, cache="none", **axes)


def phase_fit(clock, x, s: Sizes, seed: int):
    import jax

    from repro.api import KernelKMeans

    est = KernelKMeans(solver_config(s, distribution="single"))

    def fit():
        est.fit(x, seed)
        jax.block_until_ready(est.state_)
        return est

    est, t = timed(clock, fit)
    iters = int(est.iters_)
    require(iters >= 1, "fit ran no step")
    require(bool(jax.numpy.all(jax.numpy.isfinite(est.state_.sqnorm))),
            "fit left non-finite center norms")
    report("fit", plan=est.plan_.name, step=est.plan_.executor.mb.step,
           iters=iters, ms_per_step=round(t["steady_s"] * 1e3 / iters, 3),
           **t)
    return est, t


def fit_step_text(est, x) -> str:
    """Compiled HLO of the Algorithm-2 step the fitted plan runs."""
    import jax
    import jax.numpy as jnp

    from repro.core.minibatch import make_step

    ex = est.plan_.executor
    step = make_step(ex.kernel, ex.mb)
    bidx = jnp.zeros((ex.mb.batch_size,), jnp.int32)
    return jax.jit(step).lower(est.state_, x, bidx).compile().as_text()


def check_assign(est, x, seed: int, interpret: bool) -> dict:
    """Pallas streaming assignment vs the XLA reference at "highest"
    precision, on one batch against the fitted state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kernel_fns import diag_of
    from repro.core.minibatch import sample_batch
    from repro.kernels import fused_step, ops

    ex = est.plan_.executor
    kern, st = ex.kernel, est.state_
    k, w = st.coef.shape
    bidx = sample_batch(jax.random.PRNGKey(seed + 1), x.shape[0],
                        ex.mb.batch_size)
    xb = x[bidx]
    sup = x[st.idx.reshape(-1)]
    diag = diag_of(kern, xb)
    kind, p0, p1, p2 = ops._dispatch(kern)
    pallas = functools.partial(
        fused_step.streaming_assign_pallas, xb, sup.reshape(k, w, -1),
        st.coef, st.sqnorm, diag, kind=kind, p0=p0, p1=p1, p2=p2,
        interpret=interpret)
    best_p, lab_p = pallas()
    best_c, _ = pallas(bf16=True)
    with jax.default_matmul_precision("highest"):
        best_r, lab_r = jax.jit(functools.partial(
            fused_step.streaming_assign_xla, kern))(
                xb, sup, st.coef, st.sqnorm, diag)
    best_p, lab_p, best_c, best_r, lab_r = (
        np.asarray(a) for a in (best_p, lab_p, best_c, best_r, lab_r))
    out = {"rows": int(xb.shape[0]),
           "label_agreement": float(np.mean(lab_p == lab_r)),
           "max_best_diff": float(np.max(np.abs(best_p - best_r))),
           "bf16_control_diff": float(np.max(np.abs(best_c - best_r))),
           "f32_vs_bf16_diff": float(np.max(np.abs(best_p - best_c))),
           "objective": float(np.mean(best_r))}
    report("assign_check", **out)
    require(np.all(np.isfinite(best_p)), "Pallas best distances not finite")
    require(out["label_agreement"] >= MIN_LABEL_AGREEMENT,
            f"Pallas/XLA label agreement {out['label_agreement']}")
    require(out["max_best_diff"] <= BEST_DIST_ATOL,
            f"Pallas/XLA best distance differs by {out['max_best_diff']}")
    return out


def reference_labels(kern, st, x, xq):
    """Plain nearest-center labels at "highest" precision."""
    import jax
    import jax.numpy as jnp

    from repro.core.kernel_fns import diag_of, kernel_cross

    k, w = st.coef.shape
    with jax.default_matmul_precision("highest"):
        cross = kernel_cross(kern, xq, x[st.idx.reshape(-1)])
        p = jnp.einsum("bkw,kw->bk", cross.reshape(-1, k, w), st.coef)
        d = diag_of(kern, xq)[:, None] - 2.0 * p + st.sqnorm[None, :]
        return jnp.argmin(d, axis=1)


def phase_predict(clock, est, x, xq, blob, s: Sizes):
    import jax
    import numpy as np

    labels, t = timed(clock, lambda: jax.block_until_ready(
        est.predict(xq, chunk=s.chunk)))
    labels = np.asarray(labels)
    require(labels.shape == (xq.shape[0],), f"predict shape {labels.shape}")
    require(labels.min() >= 0 and labels.max() < s.k, "label out of range")
    ex = est.plan_.executor
    m = min(s.check_rows, xq.shape[0])
    ref = np.asarray(jax.jit(reference_labels)(ex.kernel, est.state_, x,
                                               xq[:m]))
    agree = float(np.mean(labels[:m] == ref))
    blobs_found = labels_match(labels, blob)
    report("predict", queries=xq.shape[0], chunk=s.chunk,
           distinct_labels=int(len(np.unique(labels))),
           reference_agreement=agree, blob_agreement=blobs_found,
           queries_per_s=round(xq.shape[0] / max(t["steady_s"], 1e-9), 1),
           **t)
    require(agree >= MIN_LABEL_AGREEMENT,
            f"predict/reference label agreement {agree}")
    require(blobs_found >= MIN_BLOB_AGREEMENT,
            f"clusters match the generating blobs on {blobs_found} of "
            "the queries")
    return t


def phase_service(clock, s: Sizes, seed: int):
    from repro.service.demo import run_demo

    buckets = (64, 256, 1024)
    tel, t = timed(clock, lambda: run_demo(
        rounds=s.service_rounds, requests=s.requests,
        request_rows=s.request_rows, seed=seed, log_every=0, verbose=False,
        k=s.k, d=s.d, batch_size=s.service_batch, tau=s.service_tau,
        capacity=s.service_capacity, iters_per_round=4, publish_every=2,
        buckets=buckets))
    q, snap, lrn = tel["queue"], tel["snapshot"], tel["learner"]
    demo, progs = tel["demo"], tel["programs"]
    out = {"rounds": demo["rounds"], "publishes": lrn["publishes"],
           "admitted": q["submitted"], "served": q["served"],
           "client_rejected": demo["client_rejected"],
           "swaps": snap["swaps"], "swap_failures": snap["swap_failures"],
           "serve_compiles": progs["serve_compiles"],
           "p99_ms": tel["latency_ms"].get("p99")}
    report("service", **out, **t)
    require(demo["rounds"] == s.service_rounds,
            f"learner finished {demo['rounds']}/{s.service_rounds} rounds")
    require(lrn["publishes"] >= 1, "learner published no snapshot")
    require(q["served"] == q["submitted"] == demo["served"],
            f"served {q['served']} of {q['submitted']} admitted")
    require(q["served"] + demo["client_rejected"] == s.requests,
            "requests lost")
    require(snap["swaps"] >= 1 and snap["swap_failures"] == 0,
            f"swaps={snap['swaps']} swap_failures={snap['swap_failures']}")
    # each bucket traces its serving program once, at the first warm-up;
    # any later trace is a recompile
    require(progs["serve_compiles"] == len(buckets),
            f"serve_compiles={progs['serve_compiles']} for "
            f"{len(buckets)} buckets")
    return out, t


# ------------------------------------------------------------ four chips
def labels_match(a, b) -> float:
    """Label agreement of two clusterings after matching each label of
    ``a`` to its most frequent partner in ``b``."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    hits = 0
    for lab in np.unique(a):
        _, counts = np.unique(b[a == lab], return_counts=True)
        hits += counts.max()
    return hits / a.size


def phase_four_chips(clock, x, xq, s: Sizes, seed: int) -> dict:
    """The sharded plans on a 4-device mesh against one-device fits from
    the same seed.  ``sharded_host`` (host batch stream) draws the very
    batches of the one-device fit, so it must match it to rounding; the
    on-device ``sharded`` loop and the ``fused_restart_sharded`` lanes
    sample shard-locally and agree only up to mini-batch noise."""
    import jax
    import numpy as np

    from repro.api import KernelKMeans
    from repro.launch.mesh import make_cluster_mesh, make_fused_mesh

    mesh, fmesh = make_cluster_mesh(), make_fused_mesh(4)
    arms = {
        "one_device": (solver_config(s, distribution="single"), None),
        "sharded": (solver_config(s, distribution="sharded"), mesh),
        "sharded_host": (solver_config(s, distribution="sharded",
                                       jit=False), mesh),
        "one_device_r4": (solver_config(s, distribution="single",
                                        restarts=4), None),
        "fused_restart_sharded": (solver_config(
            s, distribution="sharded", restarts=4), fmesh),
    }
    fits = {}
    for name, (cfg, m) in arms.items():
        est = KernelKMeans(cfg, mesh=m)
        _, t = timed(clock, lambda: jax.block_until_ready(
            est.fit(x, seed).state_))
        labels, tp = timed(clock, lambda: np.asarray(
            est.predict(xq, chunk=s.chunk)))
        d = np.asarray(est.transform(xq[:s.check_rows]))
        fits[name] = (labels, float(np.mean(d.min(axis=1))),
                      one_device_labels(est, xq, s.chunk))
        placed = est.result_ if est.result_ is not None else est.state_
        devs = sorted({dev.id for leaf in jax.tree.leaves(placed)
                       for dev in leaf.sharding.device_set})
        report(name, plan=est.plan_.name,
               mesh=None if m is None else dict(m.shape), devices=devs,
               iters=np.asarray(est.iters_).tolist(),
               objective=fits[name][1], predict_s=tp["wall_s"], **t)
        if m is not None:
            require(len(devs) == m.devices.size,
                    f"{name}: state on devices {devs} of {m.devices.size}")

    out = {}
    for name, ref, rtol in (("sharded_host", "one_device", SAME_BATCH_RTOL),
                            ("sharded", "one_device", OBJECTIVE_RTOL),
                            ("fused_restart_sharded", "one_device_r4",
                             OBJECTIVE_RTOL)):
        lab, obj, lab_local = fits[name]
        lab0, obj0, _ = fits[ref]
        row = {"vs": ref,
               "objective_rel_diff": abs(obj - obj0) / abs(obj0),
               "label_agreement": float(np.mean(lab == lab0)),
               "matched_label_agreement": labels_match(lab, lab0),
               "serving_agreement": float(np.mean(lab == lab_local))}
        out[name] = row
        report("compare", arm=name, **row)
        require(row["objective_rel_diff"] <= rtol,
                f"{name} objective {obj} vs {ref} {obj0}")
        require(row["serving_agreement"] >= MIN_LABEL_AGREEMENT,
                f"{name}: sharded predict vs one-device serving of the "
                f"same model agree on {row['serving_agreement']}")
        if name == "sharded_host":
            require(row["label_agreement"] >= MIN_LABEL_AGREEMENT,
                    f"{name} label agreement {row['label_agreement']} "
                    f"vs {ref}")
    return out


def one_device_labels(est, xq, chunk: int):
    """Labels of ``est``'s fitted model served on one device, whatever
    mesh it was fitted on."""
    import jax
    import numpy as np

    from repro.core.minibatch import assign_chunked

    kern, sup, coef, sqnorm = jax.device_get(est._serving_tuple())
    fn = jax.jit(functools.partial(assign_chunked, chunk=chunk))
    return np.asarray(fn(kern, coef, sqnorm, sup, xq))


# The host-stream sharded fit draws the one-device fit's own batches: only
# kernel rounding separates them (the sharded step contracts through a
# different Pallas kernel), so objectives agree to 1e-3 and labels to
# MIN_LABEL_AGREEMENT.  The shard-locally sampled plans follow other
# mini-batch paths from the same k-means++ centers, so their objectives
# agree only to mini-batch noise (a few percent) and their clusterings may
# settle differently; their labels are held instead to the same model
# served on one device.
SAME_BATCH_RTOL = 1e-3
OBJECTIVE_RTOL = 0.05


# ------------------------------------------------------------ main
def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(clock, s: Sizes, seed: int, interpret: bool) -> None:
    for line in cuts(s):
        report("cut", reason=line)
    (x, xq, blob), t = timed(clock, lambda: make_data(s, seed))
    report("data", n=s.n, d=s.d, k=s.k, kappa=s.kappa,
           spread=round(s.spread(), 5), queries=s.queries, **t)
    est, _ = phase_fit(clock, x, s, seed)
    if not interpret:
        txt = fit_step_text(est, x)
        require("tpu_custom_call" in txt,
                "compiled fit step holds no Pallas kernel (tpu_custom_call)")
        report("fit_step", pallas_kernels=txt.count("tpu_custom_call"))
    check_assign(est, x, seed, interpret)
    phase_predict(clock, est, x, xq, blob, s)
    phase_service(clock, s, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded plans on four chips, "
                         "against the one-device fit")
    args = ap.parse_args(argv)

    import jax

    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {info}", file=sys.stderr)
        return 2
    if args.four_chips and info["count"] < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {info}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    # a RuntimeWarning from the package itself (e.g. a kernel path giving
    # up) fails the run instead of scrolling past
    warnings.filterwarnings("error", category=RuntimeWarning,
                            module=r"repro(\.|$)")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    s = paper_sizes()
    report("run", jax=jax.__version__, device=info, compile_cache=cache,
           sizes=json.dumps(dataclasses.asdict(s), separators=(",", ":")))
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            # the comparison needs the same work in every arm, not
            # convergence, scale or query throughput: five fits and
            # predicts stay cheap on four chips
            s = dataclasses.replace(s, batch=512, queries=2048,
                                    max_iters=50)
            report("cut", reason="four chips: b=tau 512, 2048 predict "
                                 "queries, max_iters 50")
            (x, xq, _), _ = timed(clock, lambda: make_data(s, args.seed))
            phase_four_chips(clock, x, xq, s, args.seed)
        else:
            run_one_chip(clock, s, args.seed, interpret=False)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report("total", wall_s=round(time.perf_counter() - t0, 3),
           compile_s=round(clock.seconds, 3),
           peak_bytes_in_use=peak_bytes())
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
