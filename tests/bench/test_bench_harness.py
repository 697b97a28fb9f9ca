"""The benchmark harness on the CPU: cells, configurations, mixes and
metrics found by name, the peaks table and the needed-work functions, the
trace reduction on a small recorded trace, and a run that finds no TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import device, spec, trace, work  # noqa: E402

BENCHMARK = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = spec.load_cell(name)
    assert cell.chips == 1
    assert spec.driver(cell.traffic["driver"]).Run
    assert spec.reference(cell.config["reference"]).center_norms
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(spec.metric_reader(m["name"]))
    # every number the cell compares has a limit in its configuration
    assert cell.config["limits"]


def test_configuration_files_match_the_benchmark():
    for c in BENCHMARK["configs"]:
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert c["file"].startswith("bench/")


def test_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell("fit.no_such_config")


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files and new entries; no file that is there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}

    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "mnist_rbf.json"))
    cfg.update(name="mnist_rbf_k20", k=20)
    (root / "bench" / "configs" / "mnist_rbf_k20.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "fit_short.json").write_text(json.dumps(
        {"driver": "fit", "check_fits": 1}))
    (root / "bench" / "metrics" / "fits_in_window.fit.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('fits')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mnist_rbf_k20",
                             "source": "x", "why": "x", "reduced": [],
                             "file": "bench/configs/mnist_rbf_k20.json"})
    bench["workloads"].append({"name": "fit_short.mnist_rbf_k20",
                               "config": "mnist_rbf_k20",
                               "traffic": "fit_short", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][0].setdefault("workloads", []).append(
        "fit_short.mnist_rbf_k20")
    bench["per_layer"].append({
        "name": "fits_in_window.fit", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "API and plan",
        "moves": "fit_points_per_s",
        "workloads": ["fit_short.mnist_rbf_k20"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("fit_short.mnist_rbf_k20", root=str(root))
    assert cell.config["k"] == 20
    assert cell.traffic["driver"] == "fit"
    assert [m["name"] for m in cell.per_layer] == ["fits_in_window.fit"]
    read = spec.metric_reader("fits_in_window.fit", root=str(root))

    class Ctx:
        counters = {"fits": 3}

    assert read(Ctx) == 3
    assert spec.driver("fit", root=str(root)).Run
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_peaks_table_and_unknown_device():
    row = device.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_needed_work_counts_active_rows_only():
    # k=10 centers that took 20,000 points each over 100 iterations of
    # b=2048, W=2248: one row each before the first, full rings at the end
    counts = [20000.0] * 10
    assert work.fit_active_rows(1, 100, counts, 2248) == 10
    assert work.fit_active_rows(101, 100, counts, 2248) == 10 * 2248
    # half way, each ring holds 1 + 10,000 points at most W of them
    assert work.fit_active_rows(51, 100, counts, 2248) == 10 * 2248
    assert work.fit_active_rows(11, 100, counts, 2248) == 10 * 2001
    f, b = work.cross_work(2048, 1000, 1024)
    assert f == 2 * 2048 * 1000 * 1024
    assert b == 4 * (2048 + 1000) * 1024
    flops, nbytes = work.fit_work(2, [8.0, 8.0], 8, 16, 32)
    # passes of iteration 1 against 2 and 10 rows, of iteration 2
    # against 10 and 18
    want = sum(work.cross_work(8, a, 32)[0] for a in (2, 10, 10, 18))
    assert flops == want and nbytes > 0


def test_needed_work_follows_each_centers_own_points():
    """A center that takes few points keeps few rows for the whole fit:
    its empty slots are never counted, as they would be if every center
    were assumed to take b / k points an iteration."""
    w = 2248
    lopsided = [40000.0 - 9 * 50.0] + [50.0] * 9
    end = work.fit_active_rows(201, 200, lopsided, w)
    assert end == w + 9 * 51
    assert work.fit_active_rows(201, 200, [4000.0] * 10, w) == 10 * w
    assert work.fit_work(200, lopsided, 200, w, 784)[0] < \
        work.fit_work(200, [4000.0] * 10, 200, w, 784)[0]


def test_roofline_share_names_its_bound():
    peak = device.peaks("TPU v5 lite")
    share, bound = work.roofline_share(197e12, 1.0, 2.0, peak)
    assert bound == "flops" and share == pytest.approx(50.0)
    share, bound = work.roofline_share(1.0, 819e9, 4.0, peak)
    assert bound == "bytes" and share == pytest.approx(25.0)
    # a kernel that skips empty slots is held to the same needed work, so
    # it reads higher only by being faster, and the needed time never
    # exceeds the measured time of a kernel that does at least that work
    assert work.roofline_share(1.0, 1.0, 0.0, peak) is None


def _events():
    ms = 1e6
    ops = [("%while.1 = (f32[8]) while(...)", 1 * ms, 3 * ms,
            "/device:TPU:0"),                               # holds the next
           ("%streaming_assign_pallas.3 = (f32[8]) custom-call(...)",
            1.5 * ms, 1 * ms, "/device:TPU:0"),
           ("%fusion.2 = f32[8] fusion(...)", 2.5 * ms, 1 * ms,
            "/device:TPU:0"),
           ("%fusion.3 = f32[8] fusion(...)", 8 * ms, 1 * ms,
            "/device:TPU:0"),
           ("%outside = f32[8] fusion(...)", 20 * ms, 5 * ms,
            "/device:TPU:0")]
    spans = [("bench.window", 0.0, 10 * ms),
             ("bench.fit", 0.5 * ms, 4 * ms),
             ("bench.sleep", 4.5 * ms, 3.5 * ms)]
    return trace.Events(ops=ops, spans=spans)


def test_reduce_synthetic_trace():
    from benchlib import readers

    s = trace.reduce(_events())
    assert s.window_s == pytest.approx(0.010)
    # union [1, 4] and [8, 9] ms
    assert s.busy_s == pytest.approx(0.004)
    assert s.idle_share == pytest.approx(0.6)
    # the loop op holds the kernel and a fusion: only those count by name
    assert "%while.1" not in s.op_s
    assert s.op_s["%streaming_assign_pallas.3"] == pytest.approx(0.001)
    assert s.op_s["%fusion.2"] == pytest.approx(0.001)
    assert "%outside" not in s.op_s
    assert readers.pallas_s(s) == pytest.approx(0.001)
    assert readers.other_s(s) == pytest.approx(0.002)
    gaps = dict(s.idle_gaps)
    # each gap goes whole to the innermost span over its midpoint
    assert gaps["bench.fit"] == pytest.approx(0.001)        # [0, 1]
    assert gaps["bench.sleep"] == pytest.approx(0.004)      # [4, 8]
    assert gaps["host: none"] == pytest.approx(0.001)       # [9, 10]
    assert sum(gaps.values()) == pytest.approx(0.006)


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three passes of the streaming
    Pallas kernel and an XLA fusion inside bench.fit spans, 5 ms sleeps
    between them.  Recorded with ``trace.capture`` and ``load_events``,
    the Events tuples dumped as gzipped JSON."""
    from benchlib import readers

    ev = trace.read_events(os.path.join(BENCH, "testdata",
                                        "trace_small.json.gz"))
    s = trace.reduce(ev)
    assert 0 < s.busy_s < s.window_s
    assert readers.pallas_s(s) > 0 and readers.other_s(s) > 0
    gaps = dict(s.idle_gaps)
    assert gaps.get("bench.sleep", 0) >= 3 * 0.004
    assert s.spans["bench.fit"][0] == 3


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_outside_a_checkout_of_the_program_fails(tmp_path):
    """With only BENCHMARK.json and the benchmark's own files there is no
    system to run: a run that gets past the chip check still ends with no
    result and a non-zero exit."""
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    code = ("import sys; sys.path.insert(0, 'bench'); import json, run; "
            "rc, res = run.run(['--workload', %r, '--seed', '1', "
            "'--seconds', '1'], require_tpu=False); "
            "print(json.dumps(res)); sys.exit(rc)" % CELLS[0])
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "repro" in out.stderr
