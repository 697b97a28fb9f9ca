"""chip_smoke.py rehearsed on the CPU at a tiny size: its phase functions
run end to end (Pallas kernels in interpret mode), and ``main()`` refuses
to run, with a non-zero exit and no result line, where JAX finds no TPU."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()

TINY = smoke.Sizes(n=2048, d=16, k=8, kappa=2.0, batch=64, queries=512,
                   chunk=128, check_rows=128, service_batch=64,
                   service_tau=32, service_rounds=2, service_capacity=512,
                   requests=8, request_rows=32)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_phases_end_to_end_tiny(capsys):
    clock = smoke.CompileClock()
    smoke.run_one_chip(clock, TINY, seed=0, interpret=True)
    out = capsys.readouterr().out
    for phase in ("# data:", "# fit:", "# assign_check:", "# predict:",
                  "# service:"):
        assert phase in out, out
    assert clock.seconds > 0


def test_failed_check_raises():
    with pytest.raises(smoke.SmokeFailure):
        smoke.require(False, "broken phase")


def test_labels_match_is_permutation_invariant():
    assert smoke.labels_match([0, 0, 1, 1], [5, 5, 3, 3]) == 1.0
    assert smoke.labels_match([0, 0, 1, 1], [5, 3, 3, 3]) == 0.75


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on 4 virtual CPU devices, in a child process
    (the device count is fixed when a process starts)."""
    script = textwrap.dedent("""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = smoke
        spec.loader.exec_module(smoke)
        s = smoke.Sizes(n=2048, d=16, k=8, kappa=2.0, batch=64,
                        queries=512, chunk=128, check_rows=256,
                        max_iters=50)
        clock = smoke.CompileClock()
        x, xq, _ = smoke.make_data(s, 0)
        out = smoke.phase_four_chips(clock, x, xq, s, 0)
        assert out["sharded_host"]["label_agreement"] == 1.0, out
        print("FOUR-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR-OK" in r.stdout, r.stdout[-2000:]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to .jax_cache/ at the checkout root."""
    from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv(CACHE_ENV, str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(CACHE_ENV, raising=False)
            want = os.path.join(ROOT, ".jax_cache")
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
