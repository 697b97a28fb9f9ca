"""The compiled init draw (``repro.core.init.draw_init``): one program per
(method, k, kernel signature), the same indices as the eager
``kmeans_plus_plus`` / ``random_init``, and nothing built or compiled when
a later fit draws at the same shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import loop
from repro.core.init import draw_init, kmeans_plus_plus, random_init
from repro.core.kernel_fns import (
    Gaussian, Polynomial, Precomputed, kernel_cross,
)

N, D, K = 384, 8, 6
KEYS = [jax.random.PRNGKey(s) for s in (0, 7, 2 ** 31 + 5)]


def _points(seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (N, D))


def _gram(seed=0):
    x = _points(seed)
    return Precomputed(gram=kernel_cross(Gaussian(kappa=jnp.float32(8.0)),
                                         x, x))


def _case(name):
    """(kernel, data, method) for one parametrised kernel."""
    if name == "gaussian":
        return Gaussian(kappa=jnp.float32(8.0)), _points(), "kmeans++"
    if name == "polynomial":
        return (Polynomial(bias=jnp.float32(1.0), scale=jnp.float32(D),
                           degree=3), _points(), "kmeans++")
    if name == "precomputed":
        xi = jnp.arange(N, dtype=jnp.float32)[:, None]
        return _gram(), xi, "kmeans++"
    return Gaussian(kappa=jnp.float32(8.0)), _points(), "random"


@pytest.mark.parametrize("name", ["gaussian", "polynomial", "precomputed",
                                  "random"])
def test_compiled_draw_matches_eager(name):
    kernel, x, method = _case(name)
    for key in KEYS:
        got = draw_init(key, x, K, kernel, method)
        want = (kmeans_plus_plus(key, x, K, kernel) if method == "kmeans++"
                else random_init(key, x.shape[0], K))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert len(set(np.asarray(got).tolist())) == K


class _Compiles:
    """Counts jax.monitoring's trace, lowering and compile events."""

    def __enter__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@pytest.mark.parametrize("name", ["gaussian", "precomputed"])
def test_second_draw_builds_nothing(name):
    """A second draw at the same shape, from another key, builds no
    program and compiles nothing; for a Precomputed kernel the second
    draw is on a new Gram of the same shape, and reads that Gram."""
    kernel, x, method = _case(name)
    draw_init(KEYS[0], x, K, kernel, method)
    second = (Precomputed(gram=_gram(seed=1).gram) if name == "precomputed"
              else kernel)
    key = KEYS[1]
    builds = loop.program_builds()
    with _Compiles() as compiles:
        got = draw_init(key, x, K, second, method)
        jax.block_until_ready(got)
    assert loop.program_builds() == builds
    assert compiles.events == 0
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(kmeans_plus_plus(key, x, K, second)))
    if name == "precomputed":
        first = draw_init(key, x, K, kernel, method)
        assert not np.array_equal(np.asarray(got), np.asarray(first))


def test_draw_is_keyed_by_kernel_value():
    """Small kernels are closed over and keyed by value: a draw for one
    kappa never serves another."""
    x = _points()
    a, b = Gaussian(kappa=jnp.float32(0.5)), Gaussian(kappa=jnp.float32(64.0))
    loop.clear_program_cache()
    draw_init(KEYS[0], x, K, a)
    builds = loop.program_builds()
    got = draw_init(KEYS[2], x, K, b)
    assert loop.program_builds() == builds + 1
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(kmeans_plus_plus(KEYS[2], x, K, b)))


def test_unknown_method_is_refused():
    with pytest.raises(ValueError, match="unknown init method"):
        draw_init(KEYS[0], _points(), K, Gaussian(kappa=jnp.float32(1.0)),
                  "kmeans||")
