"""Serving launcher: batched prefill + decode loop, plus the clustering
serving path (multi-restart fit -> sharded assignment of large query sets)
and the always-on service demo (repro.service learner/actor split).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --batch 4 --prompt-len 32 --gen 16

    # clustering: fit best-of-R on-device, then serve sharded predictions
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --cluster --restarts 4 \
        --n 8192 --queries 65536 --k 8

    # serve from a published snapshot instead of refitting in-process
    PYTHONPATH=src python -m repro.launch.serve --cluster \
        --snapshot centers.npz --queries 65536

    # always-on service: learner thread publishing snapshots, actor
    # microbatching requests against the latest one
    PYTHONPATH=src python -m repro.launch.serve --service \
        --rounds 12 --requests 200
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def serve_lm(args):
    from repro.configs import get_config
    from repro.models import decode_step, init_params, prefill

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(dtype="float32")
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving "
                         "(see DESIGN.md skip notes)")

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    b, s = args.batch, args.prompt_len
    key = jax.random.PRNGKey(args.seed + 1)
    if cfg.frontend == "stub":
        batch = {"embeds": jax.random.normal(key, (b, s, cfg.frontend_dim))}
    else:
        batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab)}

    cache_len = s + args.gen + 8
    t0 = time.time()
    logits, cache = prefill(params, cfg, batch, cache_len=cache_len)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    dstep = jax.jit(lambda p, c, t, q: decode_step(p, cfg, c, t, q),
                    donate_argnums=(1,))
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((b,), s + i, jnp.int32)
        logits, cache = dstep(params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={b} prompt={s} generated={args.gen}")
    print(f"prefill: {t_prefill * 1e3:.1f} ms "
          f"({b * s / t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode * 1e3:.1f} ms "
          f"({b * (args.gen - 1) / max(t_decode, 1e-9):.0f} tok/s)")
    print("sample token ids:", gen[0, :10].tolist())


def serve_cluster(args):
    """Fit best-of-R through the KernelKMeans estimator (the restart axis
    device-sharded), then serve sharded batch assignment — the clustering
    analogue of prefill+decode: one expensive fit, then high-throughput
    predict over query shards.

    With ``--snapshot PATH`` the fit is skipped entirely: the estimator
    is rebuilt from a published snapshot (``KernelKMeans.load`` — the
    same file the service's learner publishes) and serves from it; the
    fitting and serving processes need share nothing but that file."""
    from repro.api import KernelKMeans, SolverConfig
    from repro.data import blobs
    from repro.launch.mesh import make_restart_mesh

    x, _ = blobs(n=args.n, d=args.d, k=args.k, seed=args.seed)
    x = jnp.asarray(x)

    if args.snapshot:
        t0 = time.time()
        est = KernelKMeans.load(args.snapshot)
        print(f"cluster serve: loaded snapshot {args.snapshot} "
              f"(k={est.config.k}, kernel={est.config.kernel!r}) "
              f"in {(time.time() - t0) * 1e3:.1f} ms — no in-process fit")
    else:
        cfg = SolverConfig(k=args.k, batch_size=args.batch_size,
                           tau=args.tau, max_iters=args.max_iters,
                           epsilon=-1.0, kernel="rbf",
                           kernel_params={"kappa": 1.0}, cache="none",
                           distribution="single", restarts=args.restarts)
        mesh = make_restart_mesh(args.restarts)
        est = KernelKMeans(cfg, mesh=mesh)

        t0 = time.time()
        res = est.fit(x, key=args.seed).result_
        jax.block_until_ready(res.objectives)
        t_fit = time.time() - t0
        print(f"cluster fit [{est.plan_.name}]: R={args.restarts} on "
              f"{mesh.devices.size} device(s) "
              f"in {t_fit * 1e3:.1f} ms; best objective "
              f"{float(res.objective):.4f} (restart {int(res.best)}, "
              f"per-restart {[round(float(o), 4) for o in res.objectives]})")
        if args.save_snapshot:
            est.save_atomic(args.save_snapshot)
            print(f"saved snapshot -> {args.save_snapshot}")

    xq = jnp.tile(x, (-(-args.queries // args.n), 1))[:args.queries]
    pred = est.predict(xq)                     # warm compile
    pred.block_until_ready()
    t0 = time.time()
    pred = est.predict(xq)
    pred.block_until_ready()
    t_pred = time.time() - t0
    where = ("from snapshot" if args.snapshot
             else f"sharded over {est.mesh.devices.size} device(s)")
    print(f"serve: {xq.shape[0]} queries in {t_pred * 1e3:.1f} ms "
          f"({xq.shape[0] / max(t_pred, 1e-9):.0f} assignments/s, "
          f"{where})")
    print("cluster sizes:",
          jnp.bincount(pred, length=est.config.k).tolist())


def serve_cluster_cached(args):
    """Serving demo for the Gram tile cache subsystem (repro.cache):

    fit with the nested sampler warming a device-resident tile cache, then
    serve repeated-row query batches through ``predict_cached`` — the
    hit/miss/eviction counters are the measured kernel-evaluation telemetry
    (every miss = tile x n evaluations; hits are pure gathers).

    ``--cache-mode precomputed`` swaps the LRU for the full-Gram fast path
    (PrecomputedGram) — the right call when n^2 fits on device."""
    from repro.api import KernelKMeans, SolverConfig
    from repro.cache import predict_cached, stats
    from repro.data import blobs

    x, _ = blobs(n=args.n, d=args.d, k=args.k, seed=args.seed)
    x = jnp.asarray(x)
    cfg = SolverConfig(k=args.k, batch_size=args.batch_size, tau=args.tau,
                       max_iters=args.max_iters, epsilon=-1.0,
                       kernel="rbf", kernel_params={"kappa": 1.0},
                       distribution="single", jit=False,
                       cache_tile=args.cache_tile,
                       cache_capacity=args.cache_capacity)

    if args.cache_mode == "precomputed":
        est = KernelKMeans(cfg.replace(cache="precomputed"))
        t0 = time.time()
        est.fit(x, key=args.seed)
        hist = est.history_
        print(f"precomputed-Gram fit [{est.plan_.name}]: {len(hist)} iters "
              f"in {(time.time() - t0) * 1e3:.1f} ms "
              f"({args.n * args.n} kernel evals once, 0 per iteration)")
        xq = jnp.tile(x, (-(-args.queries // args.n), 1))[:args.queries]
        est.predict(xq).block_until_ready()       # warm compile
        t0 = time.time()
        pred = est.predict(xq)
        pred.block_until_ready()
        t_pred = time.time() - t0
        print(f"serve: {xq.shape[0]} queries in {t_pred * 1e3:.1f} ms "
              f"({xq.shape[0] / max(t_pred, 1e-9):.0f} assignments/s)")
        print("cluster sizes:", jnp.bincount(pred, length=args.k).tolist())
        return

    est = KernelKMeans(cfg.replace(cache="lru", sampler="nested"))
    t0 = time.time()
    est.fit(x, key=args.seed)
    jax.block_until_ready(est.state_.sqnorm)
    t_fit = time.time() - t0
    state, ck, hist = est.state_, est.cache_, est.history_
    s = stats(ck.cache)
    print(f"cached fit [{est.plan_.name}]: {len(hist)} iters in "
          f"{t_fit * 1e3:.1f} ms — "
          f"hits {s['hits']} misses {s['misses']} "
          f"evictions {s['evictions']} "
          f"(hit rate {s['hit_rate']:.2%}, {s['evals']} kernel evals)")

    # repeated-row query stream: the serving regime the cache targets
    qidx = jnp.tile(jnp.arange(args.n, dtype=jnp.int32),
                    -(-args.queries // args.n))[:args.queries]
    pred, ck = predict_cached(ck, state, qidx, chunk=4096)  # warm compile
    pred.block_until_ready()
    before = stats(ck.cache)
    t0 = time.time()
    pred, ck = predict_cached(ck, state, qidx, chunk=4096)
    pred.block_until_ready()
    t_pred = time.time() - t0
    after = stats(ck.cache)
    print(f"serve: {qidx.shape[0]} queries in {t_pred * 1e3:.1f} ms "
          f"({qidx.shape[0] / max(t_pred, 1e-9):.0f} assignments/s) — "
          f"+{after['hits'] - before['hits']} hits "
          f"+{after['misses'] - before['misses']} misses "
          f"(lifetime hit rate {after['hit_rate']:.2%})")
    print("cluster sizes:", jnp.bincount(pred, length=args.k).tolist())
    # the uniform service telemetry shape (repro.service.telemetry):
    # cache counters + compile counter in the same dict every service
    # component reports through
    from repro.service import telemetry
    t = telemetry.poll(cache=ck.cache)
    print(telemetry.format_line(t))


def serve_dryrun(args):
    """``--dry-run``: resolve the clustering plan for the requested shape
    and print its lowering onto the fit-loop core (``KernelKMeans
    .explain()``) — which solver, which sampler/step body/placement, the
    donation signature, the active cross-cutting hooks and the canonical
    stage sequence — without touching data or compiling a fit.  With
    ``--cluster`` flags this describes exactly the plan ``serve
    --cluster`` would run."""
    from repro.api import KernelKMeans, SolverConfig
    from repro.launch.mesh import make_restart_mesh

    mesh = None
    kw = dict(k=args.k, batch_size=args.batch_size, tau=args.tau,
              max_iters=args.max_iters, kernel="rbf",
              kernel_params={"kappa": 1.0})
    if args.restarts > 1:
        kw.update(cache="none", distribution="single",
                  restarts=args.restarts)
        mesh = make_restart_mesh(args.restarts)
    est = KernelKMeans(SolverConfig(**kw), mesh=mesh)
    info = est.explain(n=args.n, d=args.d, deep=args.deep)
    print(f"plan [{info['plan']}] for n={info['n']}:")
    cfgline = ", ".join(f"{k}={v!r}" for k, v in info["config"].items())
    print(f"  config: {cfgline}")
    low = info["lowering"]
    for f in ("driver", "sampler", "step", "placement", "donation",
              "hooks"):
        print(f"  {f}: {low[f]}")
    print("  stages:")
    for i, s in enumerate(info["stages"]):
        print(f"    {i + 1}. {s}")
    if "compiled_step" in info:
        cs = info["compiled_step"]
        if "note" in cs:
            print(f"  compiled step: {cs['note']}")
        else:
            mem, cost = cs["memory"], cs["cost"]
            print(f"  compiled step: peak {mem['peak_bytes']} B, "
                  f"{cost['flops_per_device']:.3e} flops, "
                  f"{cost['bytes_per_device']:.3e} B accessed, "
                  f"collective {cs['collectives']['total']} B")


def serve_service(args):
    """Always-on clustering service demo (repro.service): a learner
    thread runs continuous partial_fit over the bounded ingest buffer and
    publishes versioned snapshots; an actor thread serves microbatched
    predictions from the latest snapshot with admission queueing and
    atomic swap.  Prints the uniform telemetry line per publish and a
    final summary."""
    from repro.service.demo import run_demo

    compress = "off"
    if args.compress_m:
        compress = {"m": args.compress_m, "every": args.compress_every,
                    "selector": args.compress_selector}
    t = run_demo(rounds=args.rounds, requests=args.requests,
                 request_rows=args.request_rows, seed=args.seed,
                 k=args.k, d=args.d, capacity=args.buffer_capacity,
                 batch_size=args.batch_size, tau=args.tau,
                 iters_per_round=args.iters_per_round,
                 publish_every=args.publish_every,
                 buffer_mode=args.buffer_mode,
                 arrivals_per_step=args.arrivals_per_step,
                 log_every=args.publish_every, compress=compress)
    demo = t["demo"]
    lat = t["latency_ms"]
    print(f"service: served {demo['served']} requests "
          f"(client saw {demo['client_rejected']} backpressure rejects) "
          f"over {demo['rounds']} learner rounds, snapshot versions "
          f"{demo['versions']}")
    print(f"service: p50 {lat['p50']:.2f} ms, p99 {lat['p99']:.2f} ms, "
          f"serve compiles {t['programs']['serve_compiles']}, "
          f"fit builds {t['programs']['fit_builds']}")
    sup = t.get("support")
    if sup:
        print(f"service: support rows={sup['rows']} (window W="
              f"{sup['window']}), compressions={sup['compressions']}, "
              f"m={sup['m']}, drift={sup['last_drift']}")


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # clustering serving path
    ap.add_argument("--cluster", action="store_true",
                    help="serve kernel k-means assignments instead of an LM")
    ap.add_argument("--snapshot", default=None,
                    help="serve --cluster from this saved snapshot "
                         "(KernelKMeans.load) instead of refitting "
                         "in-process")
    ap.add_argument("--save-snapshot", default=None,
                    help="after a --cluster fit, atomically save the "
                         "snapshot here (for later --snapshot serving)")
    ap.add_argument("--restarts", type=int, default=4)
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--queries", type=int, default=65536)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--tau", type=int, default=128)
    ap.add_argument("--max-iters", type=int, default=40)
    # Gram tile cache serving demo (repro.cache)
    ap.add_argument("--cache", action="store_true",
                    help="serve through the Gram tile cache with hit/miss/"
                         "eviction counters (implies --cluster)")
    ap.add_argument("--cache-mode", choices=["lru", "precomputed"],
                    default="lru")
    ap.add_argument("--cache-tile", type=int, default=512)
    ap.add_argument("--cache-capacity", type=int, default=16)
    # plan inspection (docs/architecture.md)
    ap.add_argument("--dry-run", action="store_true",
                    help="print the resolved clustering plan's lowering "
                         "onto the fit-loop core (KernelKMeans.explain) "
                         "and exit — no data, no fit")
    ap.add_argument("--deep", action="store_true",
                    help="with --dry-run: also .lower().compile() the "
                         "step program and print its HLO memory/cost "
                         "analysis")
    # always-on service demo (repro.service)
    ap.add_argument("--service", action="store_true",
                    help="run the learner/actor service demo "
                         "(docs/serving.md)")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--request-rows", type=int, default=256)
    ap.add_argument("--buffer-capacity", type=int, default=2048)
    ap.add_argument("--buffer-mode", choices=["reservoir", "nested"],
                    default="reservoir")
    ap.add_argument("--arrivals-per-step", type=int, default=512)
    ap.add_argument("--iters-per-round", type=int, default=4)
    ap.add_argument("--publish-every", type=int, default=4)
    # landmark compression (docs/compression.md)
    ap.add_argument("--compress-m", type=int, default=0,
                    help="landmark count m per center: > 0 enables "
                         "round-cadence compression in the --service "
                         "learner (serving cost O(k*m), flat in rounds)")
    ap.add_argument("--compress-every", type=int, default=0,
                    help="additionally compress in-loop every N fit "
                         "iterations (0: round cadence only)")
    ap.add_argument("--compress-selector", choices=["uniform", "leverage"],
                    default="uniform")
    args = ap.parse_args()

    if args.dry_run:
        serve_dryrun(args)
        return
    if args.service:
        serve_service(args)
        return
    if args.cache:
        serve_cluster_cached(args)
        return
    if args.cluster:
        serve_cluster(args)
        return
    if args.arch is None:
        raise SystemExit("--arch is required unless --cluster is given")
    serve_lm(args)


if __name__ == "__main__":
    main()
