"""The k-nn graph kernel on the device and Algorithm 2 on its index data,
against the plain reference (``bench/references/knn_graph.py``: float64
host arithmetic, independent of the program) at a small size on the CPU.

The fused step's index-data passes and its recompute run the Gram-row
Pallas kernel where it compiles natively; here ``ops.native`` is steered
to take that branch with the kernel in interpret mode, and the composed
step (the branch the CPU takes by itself) is what it is held to."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import checks, spec  # noqa: E402

from repro.core import minibatch  # noqa: E402
from repro.core.kernel_fns import Precomputed  # noqa: E402
from repro.core.state import init_state  # noqa: E402
from repro.data import graph_kernels  # noqa: E402
from repro.kernels import ops  # noqa: E402

REF = spec.reference("knn_graph")
N, D, K, B = 600, 16, 5, 64
NEIGHBORS = 10
with open(os.path.join(BENCH, "configs", "pendigits_knn.json")) as f:
    LIMITS = json.load(f)["limits"]["fit_gram"]


def _points(seed=0, noise=0.25, protos=10):
    """Rows of a mixture of prototypes in [0, 1]^16 whose k-nn graph is
    one piece with some edges between prototypes."""
    rng = np.random.default_rng(seed)
    p = rng.uniform(size=(protos, D))
    return (p[rng.integers(0, protos, N)]
            + noise * rng.normal(size=(N, D))).astype(np.float32)


@pytest.fixture(scope="module")
def graph():
    x = _points()
    kern, idx = graph_kernels.knn_kernel(x, k=NEIGHBORS)
    return x, kern, idx, REF.build(x, NEIGHBORS)


def _kernel_branch(monkeypatch, on: bool):
    monkeypatch.setattr(ops, "native", lambda: on)


def test_device_gram_matches_reference(graph):
    x, kern, idx, ref = graph
    assert isinstance(kern.gram, jax.Array)
    assert REF.gram_err(kern.gram, ref) == 0.0
    assert ref.affected.sum() < N // 10
    g = np.asarray(kern.gram)
    np.testing.assert_array_equal(g, g.T)
    np.testing.assert_array_equal(np.asarray(idx)[:, 0], np.arange(N))
    a = np.asarray(graph_kernels.knn_adjacency(x, k=NEIGHBORS))
    np.testing.assert_array_equal(a, a.T)
    assert (np.diag(a) == 1).all() and (a.sum(1) >= NEIGHBORS + 1).all()
    np.testing.assert_array_equal(a > 0, g > 0)


def test_reference_marks_ties():
    """Two rows at the same distance from row 0 across the last place of
    its list (itself and 10 neighbours): row 0 is a tie row, and it and
    both candidates are left out of ``gram_err``."""
    x = np.zeros((12, 2))
    x[1:11, 0] = np.arange(1, 11)
    x[11] = [-10.0, 0.0]        # as far from row 0 as row 10
    ref = REF.build(x, 10)
    assert ref.ties[0] and not ref.ties[5]
    assert ref.affected[[0, 10, 11]].all() and not ref.affected[5]


def _state_after(kern, idx, tau, steps=4):
    """A state whose windows hold a few batches (composed steps)."""
    cfg = minibatch.MBConfig(k=K, batch_size=B, tau=tau)
    step = minibatch.make_step(kern, cfg)
    state = init_state(idx, jnp.arange(0, N, N // K, dtype=jnp.int32)[:K],
                       kern, B + tau)
    for t in range(steps):
        state, _ = step(state, idx, minibatch.sample_batch(
            jax.random.PRNGKey(t), N, B))
    return state


@pytest.mark.parametrize("tau", [16, 64])
def test_fused_index_step_kernel_matches_composed_and_reference(
        graph, monkeypatch, tau):
    """tau=16: k W = 400 < n, the recompute gathers (W, W) blocks;
    tau=64: k W = 640 >= n, one pass of the whole Gram."""
    x, kern, idx, ref = graph
    assert minibatch.sqnorm_route(K, B + tau, N) == (
        "blocks" if tau == 16 else "gram")
    state = _state_after(kern, idx, tau)
    bidx = minibatch.sample_batch(jax.random.PRNGKey(9), N, B)
    cfg = minibatch.MBConfig(k=K, batch_size=B, tau=tau, step="fused")
    _kernel_branch(monkeypatch, True)
    new, info = jax.jit(minibatch.make_step(kern, cfg))(state, idx, bidx)
    _kernel_branch(monkeypatch, False)
    want, winfo = jax.jit(minibatch.make_step(
        kern, cfg._replace(step="composed")))(state, idx, bidx)
    np.testing.assert_array_equal(info.assignments, winfo.assignments)
    for f in ("idx", "head", "counts"):
        np.testing.assert_array_equal(getattr(new, f), getattr(want, f))
    np.testing.assert_allclose(new.coef, want.coef, rtol=1e-6)
    np.testing.assert_allclose(new.sqnorm, want.sqnorm, rtol=1e-5)
    np.testing.assert_allclose(info.f_after, winfo.f_after, rtol=1e-5)
    # against the reference, on the same windows
    pts = idx[new.idx]
    norms = REF.center_norms(pts, new.coef, ref.gram)
    np.testing.assert_allclose(new.sqnorm, norms, rtol=1e-5)
    d = REF.distances(idx[bidx], pts, new.coef, norms, ref.gram)
    np.testing.assert_allclose(info.f_after, d.min(axis=1).mean(),
                               rtol=1e-5)
    before = REF.distances(idx[bidx], idx[state.idx], state.coef,
                           np.asarray(state.sqnorm), ref.gram)
    gap = before[np.arange(B), info.assignments] - before.min(axis=1)
    assert gap.max() <= LIMITS["member_gap"]


def test_recompute_routes_agree(graph, monkeypatch):
    x, kern, idx, ref = graph
    state = _state_after(kern, idx, 64)
    got = {}
    for route in ("blocks", "gram"):
        for native in (False, True):
            monkeypatch.setattr(minibatch, "sqnorm_route",
                                lambda k, w, n, r=route: r)
            _kernel_branch(monkeypatch, native)
            got[route, native] = np.asarray(minibatch._gram_sqnorm(
                kern, idx, state.idx, state.coef))
    want = REF.center_norms(idx[state.idx], state.coef, ref.gram)
    for v in got.values():
        np.testing.assert_allclose(v, want, rtol=1e-5)


def test_fit_matches_reference_at_the_cells_limits(monkeypatch):
    """A whole fit through the fused step with the Gram-row kernel
    (interpret mode), compared as the cell compares it.  As in the cell,
    the mixture has one prototype per center: a center that takes a
    whole batch gets the beta rate 1, which empties its older slots, and
    ``fit_numbers`` counts slots as if no rate reached 1 (PERF.md)."""
    from repro.api import KernelKMeans, SolverConfig

    x = _points(seed=2, noise=0.3, protos=K)
    kern, idx = graph_kernels.knn_kernel(x, k=NEIGHBORS)
    ref = REF.build(x, NEIGHBORS)
    _kernel_branch(monkeypatch, True)
    cfg = dict(k=K, batch_size=B, tau=16, max_iters=12, epsilon=1e-6,
               rate="beta", sqnorm_mode="recompute", eval_mode="direct")
    est = KernelKMeans(SolverConfig(kernel=kern, step="fused", cache="none",
                                    distribution="single", **cfg))
    key = jax.random.PRNGKey(4)
    est.fit(idx, key)
    state = {f: np.asarray(getattr(est.state_, f))
             for f in ("idx", "coef", "head", "sqnorm", "counts")}
    got = checks.fit_numbers(state, int(est.iters_), key, idx,
                             {**cfg, "kappa": 0.0},
                             REF.Bound(ref.gram), control=False)
    got["gram_err"] = REF.gram_err(kern.gram, ref)
    for name, value in got.items():
        assert value <= LIMITS[name], (name, value)


def test_large_kernel_is_a_program_argument(graph):
    """A Gram too large to key by value is the compiled fit's argument,
    never a constant baked into it: a second estimator on another Gram
    of the same shape reuses the program and fits its own Gram."""
    from repro.api import KernelKMeans, SolverConfig
    from repro.core.loop import clear_program_cache, program_builds

    x, kern, idx, _ = graph
    other, _ = graph_kernels.knn_kernel(_points(seed=1), k=NEIGHBORS)
    clear_program_cache()

    def fit(k):
        return KernelKMeans(SolverConfig(
            kernel=k, k=K, batch_size=B, tau=16, max_iters=3,
            cache="none", distribution="single")).fit(idx, 2)

    first = fit(kern)
    builds = program_builds()
    second = fit(other)
    assert program_builds() == builds
    again = fit(Precomputed(gram=other.gram + 0.0))
    np.testing.assert_array_equal(second.state_.sqnorm, again.state_.sqnorm)
    assert not np.array_equal(first.state_.sqnorm, second.state_.sqnorm)
