"""Always-on serving split (repro.service): ingest determinism, learner
crash-recovery bit-exactness, snapshot atomicity + staleness, actor
microbatching correctness, telemetry shape.

Everything here shares one tiny shape family (capacity 128, d 8, k 4,
b 32, tau 16) so the executor's cross-estimator program cache compiles
each program once for the whole module.  The 8-virtual-device recovery
test runs in a subprocess (slow lane), like test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from repro.service import (
    Actor, Backpressure, IngestBuffer, Learner, SnapshotStore,
    StaleSnapshot, telemetry)
from repro.service.demo import build_service, make_source

K, D, CAP, B, TAU = 4, 8, 128, 32, 16


def _svc(tmpdir, **kw):
    kw.setdefault("k", K)
    kw.setdefault("d", D)
    kw.setdefault("capacity", CAP)
    kw.setdefault("batch_size", B)
    kw.setdefault("tau", TAU)
    kw.setdefault("iters_per_round", 2)
    kw.setdefault("arrivals_per_step", 64)
    kw.setdefault("buckets", (64,))
    return build_service(str(tmpdir), **kw)


def _carry_leaves(carry):
    return [np.asarray(x) for x in jax.tree.leaves(carry)]


def _assert_carries_identical(a, b):
    la, lb = _carry_leaves(a), _carry_leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(xa, xb)


# ------------------------------------------------------------------ ingest
@pytest.mark.parametrize("mode", ["reservoir", "nested"])
def test_buffer_pure_in_seed_and_step(mode):
    """Content after t pushes is a pure function of (seed, t): two
    independently-driven buffers agree bit-exactly; a different seed
    does not."""
    src = make_source(D, K, 64, seed=3)
    a = IngestBuffer(CAP, D, seed=7, mode=mode)
    b = IngestBuffer(CAP, D, seed=7, mode=mode)
    c = IngestBuffer(CAP, D, seed=8, mode=mode)
    for t in range(6):
        a.push(src(t))
    b.replay_to(src, 6)
    c.replay_to(src, 6)
    np.testing.assert_array_equal(a.snapshot(), b.snapshot())
    assert not np.array_equal(a.snapshot(), c.snapshot())
    assert a.pushes == b.pushes == 6
    assert a.admitted == b.admitted and a.dropped == b.dropped


@pytest.mark.parametrize("mode", ["reservoir", "nested"])
def test_buffer_replay_rewinds(mode):
    """replay_to a PAST push count rebuilds from scratch (the crash
    recovery path) and lands on the identical content."""
    src = make_source(D, K, 64, seed=0)
    buf = IngestBuffer(CAP, D, seed=1, mode=mode)
    buf.replay_to(src, 4)
    want = buf.snapshot()
    buf.replay_to(src, 9)           # advance past...
    buf.replay_to(src, 4)           # ...then rewind
    np.testing.assert_array_equal(buf.snapshot(), want)


@pytest.mark.parametrize("mode", ["reservoir", "nested"])
def test_buffer_counters_and_full(mode):
    src = make_source(D, K, 64, seed=0)
    buf = IngestBuffer(CAP, D, seed=0, mode=mode)
    assert not buf.full
    n_fill = (CAP + 63) // 64 if mode == "reservoir" else 1
    for t in range(n_fill + 2):
        buf.push(src(t))
    assert buf.full
    assert buf.pushed == (n_fill + 2) * 64
    assert 0 <= buf.admitted <= buf.pushed
    assert buf.dropped == buf.pushed - buf.admitted
    stats = buf.stats()
    assert stats["mode"] == mode and stats["full"]


def test_buffer_rejects_bad_shapes():
    buf = IngestBuffer(CAP, D)
    with pytest.raises(ValueError):
        buf.push(np.zeros((4, D + 1), np.float32))
    with pytest.raises(ValueError):
        IngestBuffer(CAP, D, mode="fifo")


# ------------------------------------------------- learner crash recovery
def test_learner_crash_recovery_bit_identical(tmp_path):
    """A learner crashed mid-stream and restored from the last published
    snapshot converges to a FitCarry BIT-IDENTICAL to an uninterrupted
    run — buffer replay + carried fit key leave nothing to drift."""
    rounds, crash_at = 8, 5

    l_a, *_ = _svc(tmp_path / "a", publish_every=2)
    carry_a = l_a.run(rounds)

    l_b, *_ = _svc(tmp_path / "b", publish_every=2)
    armed = {"on": True}

    def boom(rnd):
        if rnd == crash_at and armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected learner crash")

    l_b.on_round = boom
    carry_b = l_b.run(rounds)

    assert l_b.restores == 1
    assert l_a.rounds == l_b.rounds == rounds
    _assert_carries_identical(carry_a, carry_b)


def test_learner_publishes_resumable_snapshots(tmp_path):
    learner, _, store, buf, _ = _svc(tmp_path, publish_every=2)
    learner.run(5)           # publishes v2, v4, + final v5
    assert store.versions() == [2, 4, 5]
    v, est = store.load()
    assert v == 5
    labels = np.asarray(est.predict(buf.snapshot()))
    assert labels.shape == (CAP,) and set(labels) <= set(range(K))
    assert est.snapshot_carry() is not None       # resumable, not inert


# ------------------------------------------------------- snapshot store
def test_snapshot_never_torn(tmp_path):
    """Concurrent publishes + loads: every load sees a COMPLETE snapshot
    (write-temp-then-rename), never a partial file."""
    learner, _, store, _, _ = _svc(tmp_path)
    learner.run(1)
    est = learner.est

    stop = threading.Event()
    errors = []

    def publisher():
        v = 2
        while not stop.is_set():
            store.publish(est, v)
            v += 1

    def reader():
        while not stop.is_set():
            try:
                _, loaded = store.load()
                assert loaded.config.k == K
                assert loaded.snapshot_carry() is not None
            except Exception as e:      # noqa: BLE001 — collect, don't die
                errors.append(e)

    threads = [threading.Thread(target=publisher),
               threading.Thread(target=reader),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, errors[:3]
    assert store.publishes > 2
    # prune keeps disk bounded
    assert len(store.versions()) <= store.keep


def test_snapshot_staleness_bound(tmp_path):
    learner, _, store, _, _ = _svc(tmp_path)
    learner.run(1)
    store.publish(learner.est, 1)
    # generous bound: loads fine
    v, _ = store.load(max_age_s=60.0)
    assert v == 1
    # make the snapshot look old, then a tight bound must refuse it
    old = time.time() - 30.0
    os.utime(store.path_for(1), (old, old))
    with pytest.raises(StaleSnapshot):
        store.load(max_age_s=5.0)
    assert store.age_s(1) > 25.0


def test_actor_keeps_model_on_stale_snapshot(tmp_path):
    learner, _, store, _, _ = _svc(tmp_path)
    learner.run(1)
    actor = Actor(store, buckets=(64,), max_staleness_s=60.0)
    assert actor.try_swap(force=True)
    v0 = actor.version
    # a NEWER but too-old version must be refused, model kept, flagged
    store.publish(learner.est, v0 + 1)
    old = time.time() - 120.0
    os.utime(store.path_for(v0 + 1), (old, old))
    assert not actor.try_swap()
    assert actor.version == v0 and actor.stale
    # a fresh version clears the flag
    store.publish(learner.est, v0 + 2)
    assert actor.try_swap()
    assert actor.version == v0 + 2 and not actor.stale


def test_learner_program_builds_flat_after_round_one(tmp_path):
    """Round 1 fits from scratch and round 2 builds the resume program;
    the fixed buffer shape keeps every later round on those programs."""
    from repro.api.executors import program_builds

    learner, *_ = _svc(tmp_path, publish_every=2)
    builds = []
    learner.on_round = lambda r: builds.append(program_builds())
    learner.run(4)
    assert len(builds) == 4
    assert builds[1:] == [builds[1]] * 3


# ------------------------------------------------------------------ actor
def test_actor_microbatch_matches_direct(tmp_path):
    """Ragged concurrent requests, coalesced and padded to buckets, must
    return exactly what a direct predict/transform on each block gives."""
    learner, actor, store, _, _ = _svc(tmp_path, max_wait_ms=5.0)
    learner.run(1)
    _, est = store.load()
    actor.start()
    try:
        rng = np.random.default_rng(5)
        blocks = [rng.normal(0, 1, (m, D)).astype(np.float32)
                  for m in (3, 17, 64, 1, 150)]
        reqs = [actor.submit(xb) for xb in blocks]
        for xb, req in zip(blocks, reqs):
            got = np.asarray(req.wait(60.0))
            np.testing.assert_array_equal(got, np.asarray(est.predict(xb)))
        d = np.asarray(actor.transform(blocks[1], timeout=60.0))
        np.testing.assert_allclose(
            d, np.asarray(est.transform(blocks[1])), rtol=1e-6)
        # steady state: compile counters flat from here on
        warm = actor.serve_compiles
        for xb in blocks:
            actor.predict(xb, timeout=60.0)
        assert actor.serve_compiles == warm
        assert actor.served == 2 * len(blocks) + 1
    finally:
        actor.stop()


def test_actor_backpressure(tmp_path):
    learner, _, store, _, _ = _svc(tmp_path)
    learner.run(1)
    actor = Actor(store, buckets=(64,), queue_depth=2)   # worker NOT started
    actor.try_swap(force=True)
    actor.submit(np.zeros((4, D), np.float32))
    actor.submit(np.zeros((4, D), np.float32))
    with pytest.raises(Backpressure):
        actor.submit(np.zeros((4, D), np.float32))
    assert actor.rejected == 1
    assert actor.queue_stats()["depth"] == 2


def test_actor_swap_is_atomic_under_load(tmp_path):
    """Serving never observes a half-loaded model: requests issued across
    repeated snapshot swaps all complete with valid labels."""
    learner, actor, store, _, _ = _svc(tmp_path)
    learner.run(1)
    actor.poll_every_s = 0.02
    actor.start()
    try:
        rng = np.random.default_rng(9)
        xq = rng.normal(0, 1, (64, D)).astype(np.float32)
        for v in range(2, 8):
            store.publish(learner.est, v)
            labels = np.asarray(actor.predict(xq, timeout=60.0))
            assert labels.shape == (64,) and set(labels) <= set(range(K))
        deadline = time.time() + 10
        while actor.swaps < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert actor.swaps >= 2
        assert actor.last_swap_pause_ms is not None
    finally:
        actor.stop()


# -------------------------------------------------------------- telemetry
def test_telemetry_poll_shape(tmp_path):
    learner, actor, store, buf, _ = _svc(tmp_path)
    learner.run(1)
    actor.try_swap(force=True)
    t = telemetry.poll(buffer=buf, learner=learner, actor=actor)
    assert t["programs"]["fit_builds"] >= 1
    assert t["programs"]["serve_compiles"] == actor.serve_compiles
    assert t["ingest"]["pushes"] == buf.pushes
    assert t["learner"]["rounds"] == 1
    assert t["snapshot"]["version"] == actor.version
    assert t["queue"]["capacity"] == actor._queue.maxsize
    assert t["latency_ms"]["count"] == 0 and t["latency_ms"]["p99"] is None
    assert t["cache"] is None
    # the serving-cost gauge is reported even with compress="off"
    sup = t["support"]
    assert sup is not None
    assert sup["rows"] == sup["k"] * sup["window"]
    assert 0 < sup["active"] <= sup["rows"]
    assert sup["compressions"] == 0
    line = telemetry.format_line(t)
    assert line.startswith("svc | ") and "builds fit=" in line
    assert "support rows=" in line


def test_telemetry_without_actor_sections_none():
    t = telemetry.poll()
    assert t["queue"] is None and t["snapshot"] is None
    assert t["programs"]["serve_compiles"] is None
    assert isinstance(t["programs"]["fit_builds"], int)


# ----------------------------------------------- compressed serving path
def test_compressed_snapshots_swap_without_recompiles(tmp_path):
    """With the compress axis on, every published snapshot serves at the
    same (k*m) shape, so snapshot swaps after the first warmup trace
    nothing new — the landmark extension of the zero-recompile gate."""
    M = 8
    learner, actor, store, buf, _ = _svc(tmp_path, compress={"m": M},
                                         publish_every=1)
    learner.run(4)
    sup = learner.est.support_stats()
    assert sup["rows"] == K * M and sup["compressions"] == 4
    assert sup["window"] == M           # the serving window is now m
    assert sup["ratio"] == pytest.approx(M / (B + TAU))
    assert actor.try_swap(force=True)
    assert actor.support_stats()["rows"] == K * M
    warm = actor.serve_compiles
    assert warm > 0
    v0 = actor.version
    # further compressed snapshots: swaps re-warm at the SAME (k*m)
    # serving shapes, so the compile counter must not move
    for j in range(2):
        store.publish(learner.est, v0 + j + 1)
        assert actor.try_swap()
    assert actor.version == v0 + 2
    assert actor.serve_compiles == warm
    # the actor's padded predict serves from the compressed model
    actor.start()
    try:
        labels = actor.predict(buf.snapshot()[:40])
        assert np.asarray(labels).shape == (40,)
    finally:
        actor.stop()
    assert actor.serve_compiles == warm


def test_uncompressed_service_unchanged_by_compress_axis(tmp_path):
    """compress='off' (the default) publishes the full-window serving
    tuple exactly as before the axis existed."""
    learner, actor, store, buf, _ = _svc(tmp_path)
    learner.run(1)
    sup = learner.est.support_stats()
    assert sup["compressions"] == 0 and sup["m"] is None
    assert sup["rows"] == K * sup["window"]


# -------------------------------------------- serve.py snapshot round-trip
def test_save_atomic_snapshot_roundtrip(tmp_path):
    """The --save-snapshot / --snapshot serve path: save_atomic never
    leaves a temp file behind and the loaded estimator serves
    identically."""
    from repro.api import KernelKMeans

    learner, _, _, buf, _ = _svc(tmp_path / "svc")
    learner.run(1)
    est = learner.est
    path = str(tmp_path / "model.npz")
    est.save_atomic(path)
    assert os.path.exists(path)
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
    loaded = KernelKMeans.load(path)
    xq = buf.snapshot()[:50]
    np.testing.assert_array_equal(np.asarray(est.predict(xq)),
                                  np.asarray(loaded.predict(xq)))


# ------------------------------------------------- 8 virtual devices (slow)
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


RESILIENT_8DEV = """
    import tempfile
    import jax, numpy as np
    from repro.service.demo import build_service

    assert len(jax.devices()) == 8, jax.devices()

    def run(crash_at):
        with tempfile.TemporaryDirectory() as d:
            learner, _, store, _, _ = build_service(
                d, k=4, d=8, capacity=128, batch_size=32, tau=16,
                iters_per_round=2, publish_every=2, arrivals_per_step=64)
            if crash_at is not None:
                armed = [True]
                def boom(rnd):
                    if rnd == crash_at and armed[0]:
                        armed[0] = False
                        raise RuntimeError("injected crash")
                learner.on_round = boom
            carry = learner.run(8)
            return carry, learner.restores

    a, r_a = run(None)
    b, r_b = run(5)
    assert r_a == 0 and r_b == 1, (r_a, r_b)
    for xa, xb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    print("SERVICE-RESILIENT-OK")
"""


@pytest.mark.slow
def test_learner_recovery_bit_identical_8dev():
    """The determinism contract holds under 8 virtual devices: a crashed
    + restored learner's FitCarry is bit-identical to an uninterrupted
    run's."""
    _run(RESILIENT_8DEV, "SERVICE-RESILIENT-OK")


RESILIENT_COMPRESSED_8DEV = """
    import tempfile
    import jax, numpy as np
    from repro.service.demo import build_service

    assert len(jax.devices()) == 8, jax.devices()

    def run(crash_at):
        with tempfile.TemporaryDirectory() as d:
            learner, _, store, _, _ = build_service(
                d, k=4, d=8, capacity=128, batch_size=32, tau=16,
                iters_per_round=2, publish_every=2, arrivals_per_step=64,
                compress={"m": 8})
            if crash_at is not None:
                armed = [True]
                def boom(rnd):
                    if rnd == crash_at and armed[0]:
                        armed[0] = False
                        raise RuntimeError("injected crash")
                learner.on_round = boom
            carry = learner.run(8)
            _, sup, coef, sq = learner.est._serving
            return (carry, learner.restores,
                    tuple(np.asarray(a) for a in (sup, coef, sq)))

    a, r_a, s_a = run(None)
    b, r_b, s_b = run(5)
    assert r_a == 0 and r_b == 1, (r_a, r_b)
    for xa, xb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # the published COMPRESSED serving model is bit-identical too: the
    # landmark selection is keyed by the carried step counter
    assert s_a[1].shape == (4, 8), s_a[1].shape
    for xa, xb in zip(s_a, s_b):
        np.testing.assert_array_equal(xa, xb)
    print("SERVICE-COMPRESSED-RESILIENT-OK")
"""


@pytest.mark.slow
def test_compressed_learner_recovery_bit_identical_8dev():
    """Crash recovery through run_resilient restores a COMPRESSED learner
    bit-identically: same carry AND same published landmark serving
    model (selection is keyed by the carried step counter)."""
    _run(RESILIENT_COMPRESSED_8DEV, "SERVICE-COMPRESSED-RESILIENT-OK")
