"""The ``fit_gram`` cell on the CPU at a tiny size: the driver, its plain
reference (``bench/references/knn_graph.py``) and the needed-work
counts of its kernel metrics (``benchlib.gram_work``).  A sound run is
correct; the fp8 and bf16 controls and each planted fault are not."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from benchlib import device, gram_work, spec, trace  # noqa: E402

CELL = "fit_gram.pendigits_knn"
REF = spec.reference("knn_graph")
# the cell's shapes cut to a test run, its limits kept: as in the cell,
# one prototype per center, with noise that joins the prototypes' k-nn
# graphs into one piece
TINY = {"k": 5, "n": 600, "batch_size": 64, "tau": 16, "max_iters": 20,
        "data": {"kind": "prototypes", "centers": 5, "noise": 0.3}}


@pytest.fixture(autouse=True)
def fresh_programs():
    from repro.core.loop import clear_program_cache

    clear_program_cache()
    yield
    clear_program_cache()


def harness(*extra, config=None):
    rc, res = bench_run.run(
        ["--workload", CELL, "--seed", str(2 ** 31 + 7), "--seconds",
         "0.3", *extra], require_tpu=False,
        config_override={**TINY, **(config or {})})
    assert rc == 0
    return res


# ------------------------------------------------------------ reference
@pytest.mark.parametrize("precision,bits", [("bfloat16", 8), ("float8", 4)])
def test_operand_keeps_its_significant_bits(precision, bits):
    a = np.array([1.0, 1.0 + 2.0 ** -bits, 0.3, -5.5, 1e-3, 0.0])
    got = REF.operand(a, precision)
    # 1 + 2^-bits lies midway between two neighbours and rounds to even
    assert got[1] == 1.0 and got[-1] == 0.0
    assert np.all(np.abs(got - a) <= np.abs(a) * 2.0 ** -bits)
    if precision == "bfloat16":
        np.testing.assert_array_equal(
            got, np.asarray(jnp.asarray(a, jnp.float32).astype(
                jnp.bfloat16).astype(jnp.float32), np.float64))


def test_reference_sums_gram_lookups():
    """d(x, C_j) and <C_j, C_j> against the definitions, entry by entry."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(40, 3))
    g = REF.build(x, 4).gram
    ids = rng.integers(0, 40, (2, 6))
    coef = rng.uniform(size=(2, 6))
    pts = ids[..., None].astype(np.float32)
    norms = REF.center_norms(pts, coef, g)
    for j in range(2):
        want = sum(coef[j, a] * coef[j, b] * g[ids[j, a], ids[j, b]]
                   for a in range(6) for b in range(6))
        assert norms[j] == pytest.approx(want, rel=1e-12)
    q = np.array([[5.0], [17.0]])
    d = REF.distances(q, pts, coef, norms, g)
    for i, r in enumerate((5, 17)):
        for j in range(2):
            p = sum(coef[j, a] * g[r, ids[j, a]] for a in range(6))
            assert d[i, j] == pytest.approx(g[r, r] - 2 * p + norms[j],
                                            rel=1e-12)


# ------------------------------------------------------ whole runs, sound
def test_sound_run_is_correct():
    res = harness()
    assert res["correct"], res["checks"]
    c = spec.load_cell(CELL)
    assert set(res["checks"]) == set(c.config["limits"]["fit_gram"])
    assert res["checks"]["gram_err"]["value"] == 0.0
    assert res["attempted"] >= 1


def test_traced_run_reports_the_window():
    res = harness("--trace", "1")
    assert res["correct"]
    assert "compiles_in_window.fit" in res["metrics"]
    # the CPU runs the composed step: no Gram-row kernel op to read
    assert "gram_pass_ms_per_step.fit_gram" not in res["metrics"]
    assert res["device"]["window_s"] > 0


# ------------------------------------------------- controls and faults
@pytest.mark.parametrize("control", ["float8", "bfloat16"])
def test_precision_controls_fail(control):
    res = harness("--control", config={"control": control})
    assert not res["correct"], res["checks"]
    assert res["checks"]["gram_err"]["value"] > 1e-3
    assert res["checks"]["sqnorm_rel"]["value"] > 1e-3


def _broken_step(kind):
    from repro.api import executors

    make = executors.make_step

    def make_broken(kernel, mb):
        step = make(kernel, mb)

        def broken(state, x, bidx):
            if kind == "half_batch":
                return step(state, x, bidx[: bidx.shape[0] // 2])
            _, info = step(state, x, bidx)
            return state, info              # state returned unchanged

        return broken

    return make_broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_step_faults_fail(kind, monkeypatch):
    from repro.api import executors

    monkeypatch.setattr(executors, "make_step", _broken_step(kind))
    res = harness()
    assert not res["correct"], res["checks"]
    assert res["checks"]["counts_err"]["value"] > 0


def test_altered_assignment_fails(monkeypatch):
    from repro.core import minibatch

    dots = minibatch._batch_center_dots

    def altered(*a, **kw):
        return jnp.roll(dots(*a, **kw), 1, axis=1)

    monkeypatch.setattr(minibatch, "_batch_center_dots", altered)
    res = harness()
    assert not res["correct"], res["checks"]
    assert res["checks"]["member_gap"]["value"] > 1e-4


def test_gram_row_off_by_one_neighbour_fails(monkeypatch):
    """Row 7 of the program's k-nn lists takes row 307 (no neighbour of
    it in this draw) in place of its farthest neighbour."""
    from repro.data import graph_kernels

    knn_ids = graph_kernels._knn_ids

    def off(x, k, block):
        return knn_ids(x, k, block).at[7, k].set(307)

    monkeypatch.setattr(graph_kernels, "_knn_ids", off)
    # the Gram's program may already be traced in this process
    graph_kernels._knn_gram.clear_cache()
    try:
        res = harness()
    finally:
        graph_kernels._knn_gram.clear_cache()
    assert not res["correct"], res["checks"]
    assert res["checks"]["gram_err"]["value"] > 1e-2


# ------------------------------------------------------- needed work
def test_gram_pass_work_counts():
    # a pass of 2048 rows against 10 full rings of 2248 slots over a Gram
    # of 10,992 columns: the support's columns outnumber the Gram's
    f, y = gram_work.pass_work(2048, 22480, 10992, 10)
    assert f == 2 * 2048 * 22480
    assert y == 4 * (2048 * 10992 + 10 * 10992)
    f, y = gram_work.pass_work(8, 5, 100, 2)
    assert (f, y) == (80, 4 * (8 * 5 + 2 * 100))
    f, y = gram_work.recompute_work([3, 4], 100)
    assert (f, y) == (2 * 25, 4 * 25)
    assert gram_work.recompute_work([2248] * 10, 10992)[1] == \
        4 * 10 * 2248 ** 2
    # slots follow the even pace, capped at the fitted state's own
    assert gram_work.active_slots(1, 10, [100, 100], [2248, 30],
                                  2248) == [1, 1]
    assert gram_work.active_slots(11, 10, [100, 100], [2248, 30],
                                  2248) == [101, 30]
    # two passes an iteration, and the recompute on the "gram" route
    fl, by = gram_work.fit_work(2, [8.0, 8.0], [17, 17], b=8, w=16, n=20,
                                route="blocks")
    want = [gram_work.pass_work(8, a, 20, 2) for a in (2, 10, 10, 18)]
    assert fl == sum(f for f, _ in want) and by == sum(b for _, b in want)
    fl2, _ = gram_work.fit_work(2, [8.0, 8.0], [17, 17], b=8, w=16, n=20,
                                route="gram")
    assert fl2 == fl + 2 * (5 ** 2 + 5 ** 2) + 2 * (9 ** 2 + 9 ** 2)


def test_gram_roofline_reads_the_kernel_ops():
    class Ctx:
        peaks = device.peaks("TPU v5 lite")
        config = {"batch_size": 2048, "tau": 200, "n": 10992}
        counters = {"steps": 100, "iters": [100],
                    "center_counts": [[20480.0] * 10],
                    "active_slots": [[2248] * 10], "sqnorm_route": "gram"}
        summary = trace.Summary(
            window_s=1.0, busy_s=0.5, idle_gaps=[], spans={},
            op_s={"%cached_assign_dots_pallas": 0.05,
                  "%cached_assign_dots_pallas.1": 0.05,
                  "%cached_assign_dots_pallas.2": 0.1, "%fusion.3": 0.2})

    assert gram_work.per_step_ms(Ctx, gram_work.kernel_s) == \
        pytest.approx(2.0)
    assert gram_work.per_step_ms(Ctx, gram_work.other_s) == \
        pytest.approx(2.0)
    flops, nbytes = gram_work.fit_work(100, [20480.0] * 10, [2248] * 10,
                                       b=2048, w=2248, n=10992,
                                       route="gram")
    want = 100.0 * max(flops / (197e12 / gram_work.HIGHEST_PASSES),
                       nbytes / 819e9) / 0.2
    assert gram_work.roofline(Ctx) == pytest.approx(want)
    assert 0 < gram_work.roofline(Ctx) < 100
    Ctx.summary.op_s = {"%fusion.3": 0.2}
    assert gram_work.roofline(Ctx) is None
    assert gram_work.per_step_ms(Ctx, gram_work.other_s) is None
