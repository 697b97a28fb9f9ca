"""Deterministic fault injection + the hardened recovery spine.

Three layers of claims:

* The harness itself (:mod:`repro.service.faults`): firings are pure in
  (plan seed, site, occurrence index) — same plan, same workload, same
  trace; ``faults=None`` leaves every instrumented path bit-identical.
* The hardening each fault exposes: CRC-checked snapshots that
  quarantine + fall back, a watchdog that catches HUNG (not just slow)
  steps, the non-finite-carry guard + dead-center reseed, swap-failure
  counting + backoff, request cancel/deadline skip.
* The headline guarantee: a learner tortured by injected crashes/hangs
  recovers to a carry BIT-IDENTICAL to the fault-free run.

Shares test_service.py's tiny shape family (capacity 128, d 8, k 4) so
the cross-estimator program cache compiles once for the module.
"""
import os
import time

import jax
import numpy as np
import pytest

from repro.api.estimator import SnapshotIntegrityError
from repro.core.loop import guard_carry
from repro.service import (
    FaultPlan, FaultRule, InjectedFault, telemetry)
from repro.service.demo import build_service

pytestmark = pytest.mark.chaos     # select with -m chaos; runs in the
                                   # default (not-slow) lane too

K, D, CAP = 4, 8, 128


def _svc(tmpdir, **kw):
    kw.setdefault("k", K)
    kw.setdefault("d", D)
    kw.setdefault("capacity", CAP)
    kw.setdefault("batch_size", 32)
    kw.setdefault("tau", 16)
    kw.setdefault("iters_per_round", 2)
    kw.setdefault("arrivals_per_step", 64)
    kw.setdefault("buckets", (64,))
    return build_service(str(tmpdir), **kw)


def _leaves(carry):
    return [np.asarray(x) for x in jax.tree.leaves(carry)]


def _assert_identical(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(xa, xb)


# ------------------------------------------------------------ the harness
def test_plan_validates_sites_and_kinds():
    with pytest.raises(ValueError):
        FaultPlan([FaultRule("actor.dance", "crash")])
    with pytest.raises(ValueError):
        FaultPlan([FaultRule("actor.swap", "explode")])


def test_at_every_prob_triggers_and_trace():
    plan = FaultPlan([FaultRule("learner.step", "crash", at=(2,)),
                      FaultRule("actor.swap", "io", every=3,
                                max_fires=1)], seed=5)
    for i in range(5):
        if i == 2:
            with pytest.raises(InjectedFault):
                plan.fire("learner.step")
        else:
            plan.fire("learner.step")
    fired = 0
    for i in range(12):
        try:
            plan.fire("actor.swap")
        except OSError:
            fired += 1
    assert fired == 1                       # max_fires caps the every-rule
    assert plan.trace_list() == [("learner.step", "crash", 2),
                                 ("actor.swap", "io", 3)]


def test_prob_rule_is_pure_in_seed_and_occ():
    def run(seed):
        plan = FaultPlan([FaultRule("buffer.push", "nan", prob=0.3)],
                         seed=seed)
        out = []
        for i in range(40):
            out.append(plan.fire("buffer.push", index=i) is not None)
        return out, plan.trace_list()

    a, ta = run(11)
    b, tb = run(11)
    c, _ = run(12)
    assert a == b and ta == tb
    assert a != c                           # seed actually matters
    assert any(a)


def test_hang_aborts_and_raises():
    plan = FaultPlan([FaultRule("learner.step", "hang", at=(0,),
                                delay_s=30.0)])
    t0 = time.monotonic()
    import threading

    threading.Timer(0.05, plan.abort_hangs).start()
    with pytest.raises(InjectedFault, match="hang"):
        plan.fire("learner.step")
    assert time.monotonic() - t0 < 5.0      # aborted, not expired


def test_nan_and_corrupt_helpers_are_deterministic(tmp_path):
    plan = FaultPlan([FaultRule("buffer.push", "nan", at=(0,))], seed=3)
    ev = plan.fire("buffer.push", index=0)
    x = np.arange(80, dtype=np.float32).reshape(8, 10)
    a = plan.nan_rows(x, ev)
    b = plan.nan_rows(x, ev)
    np.testing.assert_array_equal(a, b)
    assert np.isnan(a).any() and not np.isnan(x).any()

    p = str(tmp_path / "f.bin")
    with open(p, "wb") as f:
        f.write(bytes(4096))
    plan2 = FaultPlan([FaultRule("snapshot.publish", "corrupt",
                                 at=(0,))], seed=3)
    ev2 = plan2.fire("snapshot.publish")
    plan2.corrupt_file(p, ev2)
    with open(p, "rb") as f:
        raw = f.read()
    assert raw != bytes(4096)
    assert raw[-128:] == bytes(128)         # EOCD region untouched


# ----------------------------------------------- faults=None bit-identity
def test_faults_none_is_bit_identical(tmp_path):
    """The whole instrumented spine with faults=None produces the same
    carry and the same buffer content as... itself; and the injection
    plumbing adds nothing observable (no counters, no trace)."""
    la, *_ = _svc(tmp_path / "a", publish_every=2)
    lb, *_ = _svc(tmp_path / "b", publish_every=2)
    _assert_identical(la.run(4), lb.run(4))
    assert la.guard_patched == 0 and la.guard_reseeded == 0
    assert la.stats()["watchdog_fires"] == 0


# ------------------------------------------------------------ carry guard
def test_guard_clean_carry_same_object(tmp_path):
    l, *_ = _svc(tmp_path)
    carry = l.run(2)
    guarded, rep = guard_carry(carry, seed=0)
    assert guarded is carry and rep.clean


def test_guard_repairs_poisoned_carry(tmp_path):
    l, *_ = _svc(tmp_path)
    carry = l.run(2)
    coef = np.array(carry.state.coef, copy=True)
    coef[0] = np.nan                        # kill center 0 entirely
    coef[1, 0] = np.inf                     # poison one entry of center 1
    bad = carry._replace(state=carry.state._replace(coef=coef))
    x = l.buffer.snapshot()
    kernel = l.est.plan_.executor.kernel
    guarded, rep = guard_carry(bad, x=x, kernel=kernel, seed=0)
    assert rep.patched > 0 and rep.reseeded == 1
    gcoef = np.asarray(guarded.state.coef)
    assert np.isfinite(gcoef).all()
    assert gcoef[0, 0] == 1.0               # reseeded as a single point
    assert np.isfinite(np.asarray(guarded.state.sqnorm)).all()
    # deterministic: same inputs, same repair
    guarded2, _ = guard_carry(bad, x=x, kernel=kernel, seed=0)
    _assert_identical(guarded, guarded2)


def test_nan_arrivals_survive_via_guard(tmp_path):
    """Degenerate (NaN-row) arrivals at the buffer: the fit still
    completes and every published carry is finite — the guard repaired
    whatever the poisoned batch broke."""
    plan = FaultPlan([FaultRule("buffer.push", "nan", at=(CAP,))],
                     seed=9)
    l, *_ = _svc(tmp_path, faults=plan)
    carry = l.run(3)
    for leaf in _leaves(carry):
        if np.issubdtype(leaf.dtype, np.floating):
            assert np.isfinite(leaf).all()
    assert plan.occurrences("buffer.push") >= CAP


# --------------------------------------------------------------- watchdog
def test_watchdog_catches_hung_step(tmp_path):
    """A step that HANGS (never returns) is detected at the deadline and
    recovery converges to the fault-free carry bit-identically."""
    l_clean, *_ = _svc(tmp_path / "clean", publish_every=2)
    want = l_clean.run(6)

    plan = FaultPlan([FaultRule("learner.step", "hang", at=(3,),
                                delay_s=120.0)])
    l, *_ = _svc(tmp_path / "chaos", publish_every=2, faults=plan,
                 step_timeout_s=2.0)
    got = l.run(6)
    assert l.stats()["watchdog_fires"] == 1 and l.restores == 1
    _assert_identical(want, got)


# --------------------------------------- snapshot integrity + fallback
def test_corrupt_snapshot_quarantined_and_load_falls_back(tmp_path):
    l, _, store, *_ = _svc(tmp_path, publish_every=1)
    l.run(3)                                # versions 1, 2, 3 on disk
    versions = store.versions()
    assert len(versions) == 3
    newest = versions[-1]
    with open(store.path_for(newest), "r+b") as f:
        f.seek(200)
        b = f.read(1)
        f.seek(200)
        f.write(bytes([b[0] ^ 0xFF]))
    v, est = store.load()
    assert v == versions[-2]                # fell back past the corrupt one
    assert store.quarantined == 1 and store.load_fallbacks == 1
    assert os.path.exists(store.path_for(newest) + ".corrupt")
    assert newest not in store.versions()
    assert store.latest_version() == versions[-2]   # pointer heals too
    assert est.predict(np.zeros((4, D), np.float32)) is not None


def test_explicit_version_corrupt_raises(tmp_path):
    l, _, store, *_ = _svc(tmp_path, publish_every=1)
    l.run(2)
    v = store.versions()[-1]
    with open(store.path_for(v), "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(SnapshotIntegrityError):
        store.load(v)
    assert store.quarantined == 1


def test_learner_restores_past_corrupt_snapshot(tmp_path):
    """Crash + corrupt newest snapshot: run_resilient falls back to the
    older intact version and still converges bit-identically (the
    buffer replay covers the extra rewind)."""
    l_clean, *_ = _svc(tmp_path / "clean", publish_every=2)
    want = l_clean.run(6)

    plan = FaultPlan([FaultRule("learner.step", "crash", at=(5,))])
    l, _, store, *_ = _svc(tmp_path / "chaos", publish_every=2,
                           faults=plan)

    def corrupt_newest(rnd):
        if rnd == 4:        # after v4 published, before the crash at 5
            with open(store.path_for(4), "r+b") as f:
                f.seek(300)
                b = f.read(1)
                f.seek(300)
                f.write(bytes([b[0] ^ 0xFF]))

    l.on_round = corrupt_newest
    got = l.run(6)
    assert l.restores == 1
    assert l.stats()["restore_fallbacks"] >= 1
    assert store.quarantined >= 1
    _assert_identical(want, got)


# ------------------------------------------------------- actor satellites
def test_swap_failures_counted_and_surfaced(tmp_path):
    l, actor, store, *_ = _svc(tmp_path, publish_every=1)
    l.run(2)
    plan = FaultPlan([FaultRule("actor.swap", "io", at=(0,))])
    actor.faults = plan
    store.faults = None
    with pytest.raises(OSError):
        actor.try_swap(force=True)
    # the loop counts what try_swap raises
    actor._stop.set()
    assert actor._swap_backoff_s(0) == actor.poll_every_s
    assert actor._swap_backoff_s(2) > actor.poll_every_s
    actor.swap_failures += 1                # what _swap_loop would do
    t = telemetry.poll(actor=actor)
    assert t["snapshot"]["swap_failures"] == 1
    assert "quarantined" in t["snapshot"]


def test_corrupt_publish_never_swapped_in(tmp_path):
    """An actor polling a store whose newest publish was corrupted swaps
    in the newest INTACT version instead — corrupt bytes never serve."""
    plan = FaultPlan([FaultRule("snapshot.publish", "corrupt",
                                at=(2,))], seed=4)
    l, actor, store, *_ = _svc(tmp_path, publish_every=1, faults=plan)
    l.run(3)                                # publish #2 (v3) corrupted
    assert actor.try_swap(force=True)
    assert actor.version == 2               # newest intact
    assert store.quarantined == 1
    assert actor.snapshot_stats()["quarantined"] == 1


def test_mismatched_kind_held_not_requeued(tmp_path):
    l, actor, *_ = _svc(tmp_path, publish_every=1)
    l.run(1)
    actor.try_swap(force=True)
    a = actor.submit(np.zeros((4, D), np.float32), "predict")
    b = actor.submit(np.zeros((4, D), np.float32), "transform")
    batch = actor._gather()
    assert batch == [a] and actor._held is b
    batch2 = actor._gather()                # held becomes the next head
    assert batch2[0] is b and actor._held is None
    actor._serve(batch)
    actor._serve(batch2)
    assert a.wait(5.0).shape == (4,)
    assert b.wait(5.0).shape == (4, K)


def test_cancelled_request_skipped(tmp_path):
    l, actor, *_ = _svc(tmp_path, publish_every=1)
    l.run(1)
    actor.try_swap(force=True)
    a = actor.submit(np.zeros((4, D), np.float32))
    b = actor.submit(np.ones((4, D), np.float32))
    a.cancel()
    actor._serve([a, b])
    assert actor.cancel_skipped == 1
    with pytest.raises(TimeoutError):
        a.wait(0.1)
    assert b.wait(5.0).shape == (4,)
    # deadline path: an expired deadline is equivalent to cancel
    c = actor.submit(np.zeros((4, D), np.float32), deadline_s=0.0)
    time.sleep(0.01)
    actor._serve([c])
    assert actor.cancel_skipped == 2


def test_serve_retries_transient_fault(tmp_path):
    plan = FaultPlan([FaultRule("actor.serve", "io", at=(0,))])
    l, actor, *_ = _svc(tmp_path, publish_every=1)
    l.run(1)
    actor.try_swap(force=True)
    actor.faults = plan
    r = actor.submit(np.zeros((4, D), np.float32))
    actor._serve([r])
    assert r.wait(5.0).shape == (4,)        # retried past the IOError
    assert actor.serve_retried == 1


# ------------------------------------------------------- trace replays
def test_same_plan_same_workload_same_trace(tmp_path):
    def run(sub):
        plan = FaultPlan([FaultRule("learner.step", "crash", at=(2,)),
                          FaultRule("buffer.push", "nan", prob=0.02)],
                         seed=21)
        l, *_ = _svc(tmp_path / sub, publish_every=2, faults=plan)
        carry = l.run(5)
        return plan.trace_list(), carry

    ta, ca = run("a")
    tb, cb = run("b")
    assert ta == tb and len(ta) > 0
    _assert_identical(ca, cb)


# ------------------------------------------------- faults together
def _combined_plan():
    """The 2nd publish is corrupted on disk, a crash hits step 5 (so the
    restore falls back past the corrupt snapshot) and a hang hits step 9
    (so only the watchdog saves the run)."""
    return FaultPlan([
        FaultRule("snapshot.publish", "corrupt", at=(1,)),
        FaultRule("learner.step", "crash", at=(5,)),
        FaultRule("learner.step", "hang", at=(9,), delay_s=120.0),
    ], seed=42)


def test_combined_faults_recover_bit_identical_and_replay(tmp_path):
    l_clean, *_ = _svc(tmp_path / "clean", publish_every=2)
    want = l_clean.run(8)

    def chaos_run(sub):
        plan = _combined_plan()
        l, _, store, *_ = _svc(tmp_path / sub, publish_every=2,
                               faults=plan, step_timeout_s=3.0)
        carry = l.run(8, max_restarts=5)
        return carry, plan.trace_list(), l.stats(), store

    carry_a, trace_a, stats_a, store_a = chaos_run("a")
    carry_b, trace_b, _, _ = chaos_run("b")
    _assert_identical(want, carry_a)
    _assert_identical(carry_a, carry_b)
    assert trace_a == trace_b and len(trace_a) == 3
    assert stats_a["watchdog_fires"] >= 1
    assert stats_a["restore_fallbacks"] >= 1
    assert store_a.quarantined >= 1


def test_soak_under_faults_loses_no_request(tmp_path):
    """An actor serves while the learner publishes, some publishes
    corrupted, with transient swap and serve IOErrors: every admitted
    request completes, a corrupt snapshot is quarantined, and the
    version served is an intact one."""
    plan = FaultPlan([
        FaultRule("snapshot.publish", "corrupt", every=3, max_fires=2),
        FaultRule("actor.swap", "io", at=(1,)),
        FaultRule("actor.serve", "io", at=(2, 7, 13)),
    ], seed=7)
    l, actor, store, *_ = _svc(tmp_path, publish_every=1, faults=plan)
    actor.poll_every_s = 0.05
    actor.serve_retries = 2
    l.run(2)                            # first snapshots exist
    l.start(8)                          # keep publishing, some corrupt
    actor.start()
    rng = np.random.default_rng(123)
    queries = [rng.normal(0, 1, (64, D)).astype(np.float32)
               for _ in range(8)]
    try:
        for _ in range(5):              # 40 requests in all
            reqs = [actor.submit(q) for q in queries]
            for req in reqs:
                assert req.wait(60.0).shape == (64,)
        l.join(120.0)
    finally:
        actor.stop()
        l.stop()
    # a corrupt publish may be skipped rather than quarantined when a
    # newer intact version lands first; corrupt the newest on disk so the
    # next swap must quarantine it and take the newest intact version
    # (with the transient swap fault off: the soak decides which swap
    # occurrence it would have hit)
    actor.faults = None
    newest = store.latest_version()
    with open(store.path_for(newest), "r+b") as f:
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 0xFF]))
    actor.try_swap(force=True)
    assert store.quarantined >= 1
    assert actor.version in store.versions()
    assert actor.served >= 40
