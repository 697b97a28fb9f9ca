"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: blocks off the (8, 128)
tiling, layouts Mosaic does not take, kernels that outgrow VMEM, programs
that outgrow HBM.  These tests lower the main-path Pallas kernels and the
jitted fused fit step at real widths for one chip of a ``v5e:2x2``
topology and compile them, so such a refusal fails here and not on the
chip.  Nothing runs; shapes only.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# paper_cluster widths (k, d) with the one-chip cut of b = tau
K, D, B = 256, 1024, 2048
W = 2 * B
N = 2 ** 18
# mnist_rbf widths: b=2048, tau=200, d and W off the 128 tiles
MNIST = dict(k=10, w=2248, d=784, b=2048)
# pendigits_knn: the k-nn Gram of n=10,992 rows, k=10, b=2048, tau=200
PENDIGITS = dict(k=10, w=2248, n=10992, b=2048)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: keep the cache
    off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name):
    from repro.kernels.cached_gather import cached_assign_dots_pallas
    from repro.kernels.fused_assign import fused_batch_center_dots_pallas
    from repro.kernels.fused_step import streaming_assign_pallas
    from repro.kernels.kernel_matmul import kernel_matmul_pallas

    f32, bf16 = jnp.float32, jnp.bfloat16
    stream = ((B, D), (K, W, D), (K, W), (K,), (B,))
    if name == "streaming_assign_f32":
        return (lambda *a: streaming_assign_pallas(*a, kind="gaussian",
                                                   p0=2.0),
                [(s, f32) for s in stream])
    if name == "streaming_assign_bf16":
        return (lambda *a: streaming_assign_pallas(*a, kind="gaussian",
                                                   p0=2.0, bf16=True),
                [(s, f32) for s in stream])
    if name in ("streaming_assign_mnist", "streaming_assign_mnist_b8192"):
        # the larger batch takes two tiles of 4096 rows, past the
        # default scoped VMEM
        k, w, d, b = MNIST["k"], MNIST["w"], MNIST["d"], MNIST["b"]
        b = 8192 if name.endswith("b8192") else b
        return (lambda *a: streaming_assign_pallas(*a, kind="gaussian",
                                                   p0=145.6),
                [(s, f32) for s in ((b, d), (k, w, d), (k, w), (k,), (b,))])
    if name == "fused_batch_center_dots_f32":
        return (lambda *a: fused_batch_center_dots_pallas(
            *a, kind="gaussian", p0=2.0),
            [((B, D), f32), ((K, W, D), f32), ((K, W), f32)])
    if name == "fused_batch_center_dots_bf16":
        return (lambda *a: fused_batch_center_dots_pallas(
            *a, kind="gaussian", p0=2.0),
            [((B, D), bf16), ((K, W, D), bf16), ((K, W), f32)])
    if name == "cached_assign_dots":
        # an index-data shape of the cached plans: b=1024 Gram rows over
        # n=65,536 points against the dense weights of k=64 windows
        return (lambda *a: cached_assign_dots_pallas(*a),
                [((1024, 65536), f32), ((64, 65536), f32)])
    if name == "cached_assign_dots_pendigits":
        # the k-nn Gram's passes: n=10,992, off the 512 lane tile, with
        # 2048 batch rows and the whole Gram for the recompute
        n, k = PENDIGITS["n"], PENDIGITS["k"]
        return (lambda *a: cached_assign_dots_pallas(*a),
                [((n, n), f32), ((k, n), f32)])
    if name == "kernel_matmul":
        return (lambda *a: kernel_matmul_pallas(*a, kind="gaussian",
                                                p0=2.0),
                [((8192, 784), f32), ((8192, 784), f32), ((8192, 1), f32)])
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "streaming_assign_f32", "streaming_assign_bf16",
    "streaming_assign_mnist", "streaming_assign_mnist_b8192",
    "fused_batch_center_dots_f32", "fused_batch_center_dots_bf16",
    "cached_assign_dots", "cached_assign_dots_pendigits", "kernel_matmul",
])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name)
    specs = [_spec(one_chip, shape, dt) for shape, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_fused_fit_step_compiles_for_v5e(one_chip, monkeypatch, precision):
    """The jitted Algorithm-2 step of the fused plan at paper_cluster
    widths: both batch x window passes are Pallas kernels, and the step
    fits one chip's HBM."""
    from repro.core.kernel_fns import Gaussian
    from repro.core.minibatch import MBConfig, make_step
    from repro.core.state import CenterState
    from repro.kernels import ops

    # compiling for the described chip: take the TPU branch of the kernel
    # dispatch, which asks the (CPU) default backend
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = MBConfig(k=K, batch_size=B, tau=B, step="fused",
                   compute_dtype="bfloat16" if precision == "bf16"
                   else "float32")
    state = CenterState(
        idx=_spec(one_chip, (K, W), jnp.int32),
        coef=_spec(one_chip, (K, W)), head=_spec(one_chip, (K,), jnp.int32),
        sqnorm=_spec(one_chip, (K,)), counts=_spec(one_chip, (K,)),
        step=_spec(one_chip, (), jnp.int32))
    step = make_step(Gaussian(kappa=jnp.float32(2.0)), cfg)
    compiled = jax.jit(step).lower(
        state, _spec(one_chip, (N, D)),
        _spec(one_chip, (B,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # the two passes are told apart by their stage scopes, and the
    # kernel wrapper's padding has its own
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any("/kkm.assign/" in ln for ln in calls)
    assert any("/kkm.objective/" in ln for ln in calls)
    assert "/kkm.pad/" in text
    mem = compiled.memory_analysis()
    hbm = 16 * 2 ** 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < hbm


def test_fused_index_step_compiles_for_v5e(one_chip, monkeypatch):
    """The fused step on index data at pendigits_knn's shapes: the Gram
    is an argument, both passes and the recompute (k W = 22,480 >= n, so
    one pass of the whole Gram) run the Gram-row kernel, the batch's Gram
    rows are the only (b, n) buffer, and no (b, k W) strip exists."""
    from repro.core.kernel_fns import Precomputed
    from repro.core.minibatch import MBConfig, make_step, sqnorm_route
    from repro.core.state import CenterState
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    k, w, n, b = (PENDIGITS[f] for f in ("k", "w", "n", "b"))
    assert sqnorm_route(k, w, n) == "gram"
    cfg = MBConfig(k=k, batch_size=b, tau=w - b, step="fused")
    state = CenterState(
        idx=_spec(one_chip, (k, w), jnp.int32),
        coef=_spec(one_chip, (k, w)), head=_spec(one_chip, (k,), jnp.int32),
        sqnorm=_spec(one_chip, (k,)), counts=_spec(one_chip, (k,)),
        step=_spec(one_chip, (), jnp.int32))
    compiled = jax.jit(lambda kern, st, x, bidx: make_step(kern, cfg)(
        st, x, bidx)).lower(
        Precomputed(gram=_spec(one_chip, (n, n))), state,
        _spec(one_chip, (n, 1)), _spec(one_chip, (b,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3
    for stage in ("/kkm.assign/", "/kkm.objective/", "/kkm.sqnorm/"):
        assert sum(stage in ln for ln in calls) == 1, stage
    assert f"f32[{b},{k * w}]" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * 2 ** 30
