"""The idle split by program span, on hand-made intervals and on the probe
trace, and the metric modules that read the program's spans and records."""
import os
import sys
import time
import types

import pytest

from benchmark import manifest, peaks, spantrace
from benchmark import tracereduce as tr
from benchmark.record import Run, Step

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
PROBE = os.path.join(ROOT, "benchmark", "testdata", "probe.xplane.pb")
S = tr.Span


def metric(name):
    return manifest.metric_module(ROOT, name)


def test_gaps_split_at_span_boundaries_across_threads():
    # main thread: a step [0, 100) holding execute [10, 90); worker
    # thread: a task [20, 80) holding a dispatch [30, 70) and its wait
    # [50, 70); the device is busy [40, 60)
    spans = [S("serve.step", 0, 100), S("serve.execute", 10, 90),
             S("exec.task", 20, 80), S("dispatch.x", 30, 70),
             S("dispatch.wait", 50, 70), S("jax.compile", 105, 110)]
    gaps = spantrace.split_gaps([[40, 60]], spans, 0, 120)
    want = {"serve.step": 10 + 10, "serve.execute": 10 + 10,
            "exec.task": 10 + 10, "dispatch.x": 10, "dispatch.wait": 10,
            "jax.compile": 5, tr.OTHER: 5 + 10}
    assert gaps == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_a_gap_goes_to_the_latest_starting_span_that_holds_it():
    # two threads' spans overlap without nesting: [0, 50) and [30, 80);
    # of two that start together the shorter is the inner one
    spans = [S("a", 0, 50), S("b", 30, 80), S("c", 80, 100),
             S("c.inner", 80, 90)]
    gaps = spantrace.split_gaps([], spans, 0, 100)
    assert gaps == pytest.approx({"a": 30e-9, "b": 50e-9,
                                  "c.inner": 10e-9, "c": 10e-9})
    assert spantrace.split_gaps([[0, 100]], spans, 0, 100) == {}


@pytest.fixture(scope="module")
def probe():
    return tr.reduce(PROBE), spantrace.reduce(PROBE)


def test_probe_reads_the_same_window_busy_shift_and_ops(probe):
    old, new = probe
    assert new.window == old.window and new.window_s == old.window_s
    assert new.busy_s == old.busy_s and new.busy == old.busy
    assert new.shift_ns == old.shift_ns and new.op_s == old.op_s
    assert new.n_devices == old.n_devices
    assert sum(new.gaps_s.values()) == pytest.approx(sum(old.gaps_s.values()))
    assert set(new.gaps_s) <= {"bench.wait", "bench.step", tr.OTHER}
    # the probe holds the benchmark's spans alone, all of them kept
    assert [(s.name, s.start, s.end) for s in old.spans] == \
        [(s.name, s.start, s.end) for s in new.spans]


def test_existing_metrics_read_the_same_from_both_reductions(probe):
    old, new = probe
    lo, hi = old.window
    steps = [Step(s.start * 1e-9, s.end * 1e-9) for s in old.spans
             if s.name == "bench.step"]
    read = {}
    cell = manifest.cell(manifest.load(ROOT), ROOT, "yi-9b.chat")
    for red in (old, new):
        run = Run("serve", cell.config["arch"], 1.0,
                  peaks.peak_for("TPU v5 lite"),
                  window=(lo * 1e-9, hi * 1e-9),
                  traced=(lo * 1e-9, hi * 1e-9), steps=steps, trace=red,
                  extra={"t0": lo * 1e-9})
        read[id(red)] = {m["name"]: metric(m["name"]).read(run)
                         for m in cell.per_layer if m["name"] != "compile_s"}
    assert read[id(old)] == read[id(new)]
    assert read[id(old)]["idle_share.serve"] is not None
    assert read[id(old)]["step_ms.serve"] is not None


def _serve_run(spans):
    red = tr.Reduction(window=(0, 1e9), busy_s=0.0, op_s={}, gaps_s={},
                       busy=[], spans=spans, n_devices=1)
    return Run("serve", {}, 1.0, {}, trace=red)


def test_serve_span_metrics():
    ms = 1e6
    spans = []
    for k, t in enumerate((0, 60, 125)):      # three steps, ms
        spans += [S("program.call", (t + 1) * ms, (t + 52) * ms),
                  S("dispatch.serve_step", (t + 3) * ms, (t + 51) * ms),
                  S("dispatch.launch", (t + 4) * ms, (t + 6) * ms),
                  S("dispatch.wait", (t + 6) * ms, (t + 50) * ms)]
    run = _serve_run(sorted(spans, key=lambda s: s.start))
    # launch ends at 66 and 131; the waits before them end at 50 and 110
    assert metric("step_gap_ms.serve").read(run) == pytest.approx(18.5)
    # program.call 51 ms around a 48 ms dispatch, every step
    assert metric("exec_hop_ms.serve").read(run) == pytest.approx(3.0)
    bare = _serve_run([S("bench.step", 0, 50 * ms)])
    assert metric("step_gap_ms.serve").read(bare) is None
    assert metric("exec_hop_ms.serve").read(bare) is None
    assert metric("step_gap_ms.serve").read(Run("serve", {}, 1.0, {})) \
        is None


def _decision(overhead_s, launched_at=None, done_at=None):
    d = types.SimpleNamespace(kernel="matmul", overhead_s=overhead_s)
    if done_at is not None:
        d.launched_at, d.done_at = launched_at, done_at
    return d


def test_graph_dispatch_metrics_read_the_dispatchers_record():
    # two calls of two nodes: [0, 1) and [2, 3) s
    steps = [Step(0.0, 1.0), Step(2.0, 3.0)]
    dec = [_decision(20e-6, 0.1, 0.4), _decision(30e-6, 0.4007, 0.9),
           _decision(40e-6, 2.1, 2.5), _decision(10e-6, 2.5005, 2.9)]
    run = Run("graph", {}, 1.0, {}, steps=steps, extra={"decisions": dec})
    assert metric("dispatch_us.graph").read(run) == pytest.approx(25.0)
    # 700 us and 500 us inside the calls; the pair across calls is left out
    assert metric("node_gap_us.graph").read(run) == pytest.approx(600.0)
    # a dispatcher that records no times (an older program)
    old = Run("graph", {}, 1.0, {}, steps=steps,
              extra={"decisions": [_decision(2e-5), _decision(3e-5)]})
    assert metric("node_gap_us.graph").read(old) is None
    assert metric("dispatch_us.graph").read(old) == pytest.approx(25.0)
    for m in ("node_gap_us.graph", "dispatch_us.graph"):
        assert metric(m).read(Run("graph", {}, 1.0, {})) is None


def test_compile_s_reads_the_counter_up_to_the_window(monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    from repro.obs import compile_counter

    compile_counter()
    jax.jit(lambda x: x - 2.0)(jnp.ones((5, 11))).block_until_ready()
    now = time.perf_counter()
    run = Run("graph", {}, 1.0, {}, window=(now, now + 1.0))
    value = metric("compile_s").read(run)
    assert value == pytest.approx(compile_counter().busy_s(until=now))
    assert value > 0
    assert "inside the window: 0" in capsys.readouterr().err
    # a program without the counter reads None
    monkeypatch.setitem(sys.modules, "repro.obs.telemetry",
                        types.ModuleType("repro.obs.telemetry"))
    assert metric("compile_s").read(run) is None


def test_wait_split_places_the_idle_inside_each_wait():
    # a matmul's wait [10, 100) with the device busy [30, 50) and [60, 90)
    red = tr.Reduction(window=(0, 200), busy_s=0.0, op_s={}, gaps_s={},
                       busy=[[30, 50], [60, 90], [150, 160]], n_devices=1,
                       spans=[S("dispatch.matmul", 0, 100),
                              S("dispatch.launch", 5, 10),
                              S("dispatch.wait", 10, 100)])
    assert spantrace.wait_split(red) == {"dispatch.matmul": {
        "n": 1, "lead_us": 0.02, "gaps_us": 0.01, "tail_us": 0.01}}


def test_host_cost_compares_steps_inside_and_before_the_trace():
    steps = [Step(0.0, 0.010, "512"), Step(0.010, 0.030, "4096"),
             Step(0.030, 0.041, "512"), Step(0.041, 0.063, "4096")]
    run = Run("graph", {}, 1.0, {}, window=(0.0, 0.063), steps=steps,
              traced=(0.030, 0.063))
    cost = spantrace.host_cost(run)
    assert cost["512"] == pytest.approx({"traced_ms": 11.0, "untraced_ms":
                                         10.0, "n_traced": 1,
                                         "n_untraced": 1})
    assert cost["4096"]["traced_ms"] == pytest.approx(22.0)
