"""The comparisons that decide ``correct``, on the CPU at tiny sizes.

The plain reference against float64 arithmetic; whole harness runs of
the cell (set-up, window, check) that come out correct; the
lower-precision control and faults planted under the timed path, each of
which must come out not correct under the cells' own limits."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from benchlib import checks, spec  # noqa: E402

REF = spec.reference("kernel_kmeans")

# tiny sizes a test run holds: the cell's widths cut, its limits kept;
# kappa is the median pairwise squared distance of these rows, as in the
# configuration
FIT = {"k": 16, "d": 256, "batch_size": 128, "tau": 128, "n": 4096,
       "max_iters": 5, "kappa": 47.0,
       "data": {"kind": "prototypes", "centers": 16, "noise": 0.1}}
TINY = {"fit.mnist_rbf": FIT}


@pytest.fixture(autouse=True)
def fresh_programs():
    from repro.core.loop import clear_program_cache

    clear_program_cache()
    yield
    clear_program_cache()


def harness(cell, monkeypatch, *extra, config=None, seconds="0.3"):
    """One whole run of ``cell`` on the CPU at its tiny size."""
    rc, res = bench_run.run(
        ["--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds",
         seconds, *extra], require_tpu=False,
        config_override={**TINY[cell], **(config or {})})
    assert rc == 0
    return res


# ------------------------------------------------------------ reference
def _f64_dist(xq, pts, coef, kappa):
    xq, pts, coef = (np.asarray(a, np.float64) for a in (xq, pts, coef))

    def kern(a, b):
        d2 = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
        return np.exp(-d2 / kappa)

    norms = np.array([c @ kern(p, p) @ c for p, c in zip(pts, coef)])
    p = np.stack([kern(xq, p) @ c for p, c in zip(pts, coef)], axis=1)
    return 1.0 - 2.0 * p + norms[None, :], norms


def test_reference_matches_float64():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    pts = jax.random.normal(k1, (5, 7, 32)) * 0.2
    coef = jax.random.uniform(k2, (5, 7)).at[:, 5:].set(0.0)
    xq = jax.random.normal(k3, (9, 32)) * 0.2
    want, wnorm = _f64_dist(xq, pts, coef, 2.0)
    norms = REF.center_norms(pts, coef, 2.0)
    np.testing.assert_allclose(norms, wnorm, rtol=1e-5)
    got = REF.distances(xq, pts, coef, norms, 2.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    low = REF.distances(xq, pts, coef,
                        REF.center_norms(pts, coef, 2.0, "float8"), 2.0,
                        "float8")
    assert float(jnp.max(jnp.abs(low - got))) > 1e-3


def test_float8_operand_keeps_four_significant_bits():
    a = jnp.array([1.0, 1.0625, 1.03, 0.3, -5.5, 1e-3, 0.0])
    got = np.asarray(REF.operand(a, "float8"))
    # 1.0625 lies midway between 1 and 1.125 and rounds to even
    np.testing.assert_allclose(got, [1.0, 1.0, 1.0, 0.3125, -5.5,
                                     0.0009765625, 0.0])
    assert np.all(np.abs(got - np.asarray(a)) <= np.abs(a) / 16)


def test_last_batch_follows_the_documented_key_derivation():
    from repro.api import keys

    key = jax.random.PRNGKey(3)
    _, fit_key = keys.split_init(key)
    for _ in range(4):
        fit_key, kb = keys.next_batch_key(fit_key)
    want = jax.random.randint(kb, (64,), 0, 1000, dtype=jnp.int32)
    np.testing.assert_array_equal(checks.last_batch(key, 4, 1000, 64), want)


def test_newest_run_walks_back_from_the_head():
    row = np.array([0.5, 0.1, 0.1, 0.2, 0.2, 0.2, 0.0, 0.0], np.float32)
    assert checks.newest_run(row, 6) == [5, 4, 3]
    assert checks.newest_run(row, 3) == [2, 1]
    assert checks.newest_run(row, 7) == []


def test_compare_refuses_a_number_without_limit():
    with pytest.raises(KeyError):
        checks.compare({"x": 1.0}, {})
    assert not checks.compare({"x": math.inf}, {"x": 1.0})[0].ok
    assert checks.compare({"x": 0.0}, {"x": 0.0})[0].ok


# ------------------------------------------------------ whole runs, sound
@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell, monkeypatch):
    res = harness(cell, monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    c = spec.load_cell(cell)
    assert set(res["checks"]) == set(c.config["limits"][
        c.traffic["driver"]])
    assert res["attempted"] >= 1


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    res = harness("fit.mnist_rbf", monkeypatch, "--trace", "1")
    assert res["correct"]
    assert "compiles_in_window.fit" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


# ------------------------------------------------- control and faults
def test_fit_precision_control_fails(monkeypatch):
    res = harness("fit.mnist_rbf", monkeypatch, "--control")
    assert not res["correct"], res["checks"]


def _broken_step(kind):
    from repro.api import executors

    make = executors.make_step

    def make_broken(kernel, mb):
        step = make(kernel, mb)

        def broken(state, x, bidx):
            if kind == "half_batch":
                return step(state, x, bidx[: bidx.shape[0] // 2])
            new, info = step(state, x, bidx)
            if kind == "constant_objective":
                # f_B(after) read as f_B(before): no improvement, so the
                # fit stops after its first iteration
                return new, info._replace(
                    f_after=info.f_before,
                    improvement=info.improvement * 0.0)
            return state, info              # state returned unchanged

        return broken

    return make_broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "constant_objective"])
def test_fit_step_faults_fail(kind, monkeypatch):
    from repro.api import executors

    monkeypatch.setattr(executors, "make_step", _broken_step(kind))
    res = harness("fit.mnist_rbf", monkeypatch)
    assert not res["correct"], res["checks"]
    if kind == "constant_objective":
        assert res["checks"]["stop_gap"]["value"] > 0.0


def test_fit_altered_assignment_fails(monkeypatch):
    from repro.kernels import ops

    assign = ops.streaming_assign

    def altered(kernel, xb, *a, **kw):
        best, idx = assign(kernel, xb, *a, **kw)
        return best, (idx + 1) % a[1].shape[0]

    monkeypatch.setattr(ops, "streaming_assign", altered)
    res = harness("fit.mnist_rbf", monkeypatch,
                  config={"step": "fused"})
    assert not res["correct"], res["checks"]
    assert res["checks"]["member_gap"]["value"] > 0.1
