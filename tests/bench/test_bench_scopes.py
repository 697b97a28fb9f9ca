"""The program's stages and spans in a trace (``benchlib.scopes``): the
protobuf reading of the trace's HLO modules, the stage reduction on
synthetic and recorded traces, and ``bench/stage_times.py``."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import readers, scopes, trace  # noqa: E402

DEVICE_STAGES = ("kkm.loop", "kkm.sample", "kkm.gather", "kkm.assign",
                 "kkm.update", "kkm.sqnorm", "kkm.objective", "kkm.pad",
                 "kkm.init")


# --------------------------------------------- a protobuf writer for tests
def _varint(v: int) -> bytes:
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        out += bytes([b | (0x80 if v else 0)])
        if not v:
            return out


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v)


def _msg(num: int, *parts) -> bytes:
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _instruction(name, opcode, op_name="", called=()):
    meta = _msg(7, _msg(2, op_name)) if op_name else b""
    calls = _msg(38, b"".join(_varint(c) for c in called)) if called \
        else b""
    return _msg(2, _msg(1, name), _msg(2, opcode), meta, calls)


def _module():
    """An HloModuleProto whose fusion root lost its op name, as XLA's
    scatter rewrites leave it, beside a fusion and a kernel that kept
    theirs."""
    fused = _msg(3, _msg(1, "fused_computation.5"), _int(5, 2),
                 _instruction("param_0", "parameter"),
                 _instruction("transpose.1", "transpose",
                              "jit(run)/kkm.loop/while/body/kkm.update/t"),
                 _instruction("scatter.2", "scatter"))
    entry = _msg(3, _msg(1, "main"), _int(5, 1),
                 _instruction("fusion.79", "fusion", called=(2,)),
                 _instruction("fusion.80", "fusion",
                              "jit(run)/kkm.loop/while/body/kkm.sqnorm/dot",
                              called=(2,)),
                 _instruction("streaming_assign_pallas", "custom-call",
                              "jit(run)/kkm.loop/while/body/kkm.assign/"
                              "jit(streaming_assign_pallas)/pallas_call"),
                 _instruction("copy.21", "copy"))
    return _msg(1, "jit_run") + fused + entry


def test_op_names_resolve_fusions_and_kernels():
    names = scopes.op_names(_module())
    assert scopes.stage_of(names["fusion.79"]) == "kkm.update"
    assert scopes.stage_of(names["fusion.80"]) == "kkm.sqnorm"
    assert scopes.stage_of(names["streaming_assign_pallas"]) == \
        "kkm.assign"
    assert scopes.stage_of(names["copy.21"]) == scopes.UNSCOPED


def test_op_names_read_a_compiled_module():
    def f(x):
        with jax.named_scope("kkm.assign"):
            y = jnp.sin(x) * 2.0 + 1.0
        with jax.named_scope("kkm.update"):
            return jnp.sum(y @ y.T, axis=0)

    compiled = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    names = scopes.op_names(module.as_serialized_hlo_module_proto())
    stages = {scopes.stage_of(p) for p in names.values()}
    assert {"kkm.assign", "kkm.update"} <= stages


def test_hlo_modules_come_from_the_metadata_plane():
    module = _module()
    hlo_proto = _msg(1, module)
    stat = _msg(5, _int(1, 3), _msg(6, hlo_proto))
    entry = _msg(4, _int(1, 7), _msg(2, _int(1, 7), _msg(2, "jit_run(7)"),
                                     stat))
    device = _msg(1, _int(1, 1), _msg(2, "/device:TPU:0"),
                  _msg(3, _msg(2, "XLA Ops")))
    meta = _msg(1, _int(1, 2), _msg(2, scopes.METADATA_PLANE), entry)
    got = scopes.hlo_modules(device + meta)
    assert got == {"jit_run(7)": module}


def test_stage_of_takes_the_innermost_stage():
    path = ("jit(run)/kkm.loop/while/body/kkm.assign/kkm.pad/"
            "jit(_pad)/pad:")
    assert scopes.stage_of(path) == "kkm.pad"
    assert scopes.stage_of("jit(run)/kkm.loop/while:") == "kkm.loop"
    assert scopes.stage_of("jit(scan)/while/body/dot_general:") == \
        scopes.UNSCOPED
    assert scopes.stage_of("") == scopes.UNSCOPED


# ------------------------------------------------------------ reduction
def _events():
    ms = 1e6
    dev = "/device:TPU:0"
    loop = "jit(run)/kkm.loop/while"
    body = loop + "/body/"
    ops = [("%while.1 = (f32[8]) while(...)", 4.5 * ms, 4 * ms, dev, loop),
           ("%fusion.3 = f32[8] fusion(...)", 4.5 * ms, 0.5 * ms, dev,
            body + "kkm.assign/kkm.pad/jit(_pad)/pad"),
           ("%streaming_assign_pallas = (f32[8]) custom-call(...)",
            5 * ms, 1 * ms, dev,
            body + "kkm.assign/jit(streaming_assign_pallas)/pallas_call"),
           ("%fusion.79 = s32[8] fusion(...)", 6 * ms, 0.5 * ms, dev,
            body + "kkm.update/transpose"),
           ("%streaming_assign_pallas.1 = (f32[8]) custom-call(...)",
            6.5 * ms, 1 * ms, dev,
            body + "kkm.objective/jit(streaming_assign_pallas)/"
                   "pallas_call"),
           ("%lt = pred[] compare(...)", 7.5 * ms, 1 * ms, dev, loop),
           ("%mul.1 = f32[8] multiply(...)", 2 * ms, 1 * ms, dev,
            "jit(multiply)/mul")]
    spans = [("bench.window", 0.0, 10 * ms),
             ("bench.fit", 0.5 * ms, 9 * ms),
             ("kkm.fit", 0.6 * ms, 4.0 * ms),
             ("kkm.init", 1.5 * ms, 2.5 * ms),
             ("kkm.run", 4.1 * ms, 0.3 * ms)]
    return trace.Events(ops=ops, spans=spans)


def test_reduce_splits_device_time_by_stage():
    s = scopes.reduce(_events())
    assert s.stage_s == pytest.approx({
        "kkm.pad": 0.0005, "kkm.assign": 0.001, "kkm.update": 0.0005,
        "kkm.objective": 0.001, "kkm.loop": 0.001,
        scopes.UNSCOPED: 0.001})
    # the loop op holds its body ops: counted once, as the base counts
    assert sum(s.stage_s.values()) == pytest.approx(
        sum(s.base.op_s.values()))
    assert s.unscoped_share == pytest.approx(0.2)
    assert s.per_step_ms(2)["kkm.assign"] == pytest.approx(0.5)


def test_reduce_gives_self_times_and_idle_under_kkm_spans():
    s = scopes.reduce(_events())
    count, total, own = s.spans["kkm.fit"]
    assert count == 1 and total == pytest.approx(0.004)
    # kkm.fit less its children kkm.init (2.5 ms) and kkm.run (0.3 ms)
    assert own == pytest.approx(0.0012)
    assert s.spans["kkm.init"] == pytest.approx((1, 0.0025, 0.0025))
    assert s.spans["bench.fit"][2] == pytest.approx(0.005)
    # each idle stretch goes to the innermost span over its midpoint
    gaps = dict(s.base.idle_gaps)
    assert gaps["kkm.fit"] == pytest.approx(0.002)         # [0, 2] ms
    assert gaps["kkm.init"] == pytest.approx(0.0015)       # [3, 4.5]
    assert gaps["bench.fit"] == pytest.approx(0.0015)      # [8.5, 10]


def test_old_trace_reads_the_same():
    """The trace recorded before the program had stages: the base numbers
    are ``trace.reduce``'s, every existing reader reads them the same, and
    every op is unscoped."""
    ev = trace.read_events(os.path.join(BENCH, "testdata",
                                        "trace_small.json.gz"))
    s = scopes.reduce(ev)
    old = trace.reduce(ev)
    assert s.base == old
    assert set(s.stage_s) == {scopes.UNSCOPED}
    assert s.stage_s[scopes.UNSCOPED] == pytest.approx(
        readers.pallas_s(old) + readers.other_s(old))


def test_recorded_fit_has_every_stage():
    """One whole fit of ``fit.mnist_rbf`` (200 iterations) on a TPU v5e,
    recorded with ``bench/stage_times.py --seconds 0 --save``: every
    device stage names some op, every step stage holds device time,
    under 2% of device time is unscoped, and the host spans are there."""
    from repro.core.loop import STAGES

    ev = trace.read_events(os.path.join(BENCH, "testdata",
                                        "trace_stages.json.gz"))
    paths = {o[4] for o in ev.ops}
    for stage in DEVICE_STAGES:
        assert any(stage in p.split("/") for p in paths), stage
    s = scopes.reduce(ev)
    # the loop op holds its body, so kkm.loop itself holds no leaf time
    for stage in set(DEVICE_STAGES) - {"kkm.loop"}:
        assert s.stage_s.get(stage, 0) > 0, stage
    assert set(s.stage_s) <= set(STAGES) | {scopes.UNSCOPED}
    assert s.unscoped_share < 0.02
    for span in ("kkm.fit", "kkm.init", "kkm.run"):
        assert s.spans[span][0] == 1, span
    # the two streaming passes are the Pallas kernels' time
    passes = s.stage_s["kkm.assign"] + s.stage_s["kkm.objective"]
    assert passes == pytest.approx(readers.pallas_s(s.base), rel=0.01)
    # the device waits on the host's k-means++ draw, and on little else
    gaps = dict(s.base.idle_gaps)
    assert gaps["kkm.init"] > 0.5 * (s.base.window_s - s.base.busy_s)


# ------------------------------------------------------ stage_times.py
def test_stage_times_without_tpu_exits_2():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "stage_times.py"),
         "--workload", "fit.mnist_rbf", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_stage_times_on_the_cpu_reports_host_spans(capsys, tmp_path):
    """At a tiny size on the CPU, whose trace has no device plane: the
    program's host spans, nested, and the saved events read back."""
    import stage_times
    from repro.core.loop import clear_program_cache

    tiny = {"k": 4, "d": 16, "batch_size": 32, "tau": 16, "n": 512,
            "max_iters": 3, "kappa": 4.0,
            "data": {"kind": "prototypes", "centers": 4, "noise": 0.1}}
    saved = str(tmp_path / "events.json.gz")
    clear_program_cache()
    try:
        rc = stage_times.main(
            ["--workload", "fit.mnist_rbf", "--seed", str(2 ** 33 + 1),
             "--seconds", "0", "--save", saved],
            require_tpu=False, config_override=tiny)
    finally:
        clear_program_cache()
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["counters"]["fits"] == 1
    assert res["counters"]["steps"] == 3
    spans = res["spans"]
    assert spans["kkm.fit"][0] == spans["kkm.init"][0] == \
        spans["kkm.run"][0] == 1
    assert spans["kkm.fit"][2] < spans["kkm.fit"][1]
    ev = trace.read_events(saved)
    assert {s[0] for s in ev.spans} >= {"bench.window", "bench.fit",
                                        "kkm.fit", "kkm.init", "kkm.run"}
