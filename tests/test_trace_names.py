"""The fit path's trace names (``repro.core.loop.STAGES``): host spans on
the profiler's clock around a fit, and device stage scopes in the
compiled fit program, the same for the fused and the composed step."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import loop

STEP_STAGES = ("kkm.sample", "kkm.gather", "kkm.assign", "kkm.update",
               "kkm.sqnorm", "kkm.objective")


def test_unknown_names_are_refused():
    assert all(s.startswith("kkm.") for s in loop.STAGES)
    with pytest.raises(ValueError):
        loop.span("kkm.nowhere")
    with pytest.raises(ValueError):
        loop.scope("assign")


def _host_spans(directory):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("kkm.")]


def test_fit_records_its_host_spans(tmp_path):
    from repro.api import KernelKMeans, SolverConfig

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 8))
    est = KernelKMeans(SolverConfig(
        k=4, batch_size=32, tau=16, max_iters=3, cache="none",
        distribution="single", kernel_params={"kappa": 8.0}))
    est.fit(x, 1)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        est.fit(x, 2)
        jax.block_until_ready(est.state_)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert sorted(s[0] for s in spans) == ["kkm.fit", "kkm.init", "kkm.run"]
    (_, f0, f1), = [s for s in spans if s[0] == "kkm.fit"]
    for name, s0, s1 in spans:
        assert f0 <= s0 <= s1 <= f1, name
    (_, i0, i1), = [s for s in spans if s[0] == "kkm.init"]
    (_, r0, _), = [s for s in spans if s[0] == "kkm.run"]
    assert i1 <= r0                     # the init draw, then the program


@pytest.mark.parametrize("step", ["fused", "composed"])
def test_fit_program_carries_every_stage(step):
    from repro.core.kernel_fns import Gaussian
    from repro.core.minibatch import (
        MBConfig, make_step, sampled_step_with_key,
    )
    from repro.core.state import init_state

    cfg = MBConfig(k=4, batch_size=16, tau=8, max_iters=2, step=step)
    kernel = Gaussian(kappa=jnp.float32(2.0))
    body = make_step(kernel, cfg)

    def run(x, init_idx, key):
        state = init_state(x, init_idx, kernel, cfg.batch_size + cfg.tau)
        return loop.run_early_stopped_keyed(
            cfg, sampled_step_with_key(body, x, cfg), state, key)

    text = jax.jit(run).lower(
        jnp.zeros((64, 8)), jnp.arange(4, dtype=jnp.int32),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "jit(run)/kkm.init/" in text
    for stage in STEP_STAGES:
        assert f"kkm.loop/while/body/{stage}/" in text, stage


@pytest.mark.parametrize("method", ["kmeans++", "random"])
@pytest.mark.parametrize("large", [False, True])
def test_init_draw_carries_its_stage(method, large):
    """The compiled init draw names its device ops ``kkm.init``, whether
    the kernel is closed over or (too large to key by value) an
    argument."""
    from repro.core import init
    from repro.core.kernel_fns import Gaussian, Precomputed

    x = jnp.zeros((64, 8))
    key = jax.random.PRNGKey(0)
    if large:
        kernel = Precomputed(gram=jnp.eye(64))
        prog = init._draw_program(4, kernel, method)
        lowered = prog.func.lower(kernel, key, x[:, :1])
    else:
        prog = init._draw_program(4, Gaussian(kappa=jnp.float32(2.0)),
                                  method)
        lowered = prog.lower(key, x)
    text = lowered.as_text(debug_info=True)
    assert "jit(draw)/kkm.init/" in text
    if method == "kmeans++":
        assert "jit(draw)/kkm.init/while/body/" in text


def test_gram_build_records_its_span_and_stage(tmp_path):
    """The k-nn Gram build: a ``kkm.gram`` host span around its dispatch
    and every device op of its program under the ``kkm.gram`` stage."""
    from repro.data import graph_kernels

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4))
    text = graph_kernels._knn_gram.lower(x, k=5, block=16).as_text(
        debug_info=True)
    assert "kkm.gram/" in text
    graph_kernels.knn_kernel(x, k=5)            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        kern, _ = graph_kernels.knn_kernel(x, k=5)
        jax.block_until_ready(kern.gram)
    finally:
        jax.profiler.stop_trace()
    assert [s[0] for s in _host_spans(str(tmp_path))] == ["kkm.gram"]
