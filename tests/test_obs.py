"""repro.obs: the telemetry/drift primitives, their integration with the
dispatcher (decision counters, gate events, residuals, <5% overhead with
telemetry attached), the executor (steal instants, queue-depth tracks),
the online refiner (refit events), the shared-epoch trace exports (Chrome
trace_event schema + Gantt CSV contract), the report CLI round-trip, and
the bench harness's schema-3 telemetry folding."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nnc import LinearModel
from repro.exec import AsyncExecutor, ExecTask, ExecutionTrace, StealPolicy
from repro.kernels import Aval
from repro.obs import (NULL_TELEMETRY, DriftConfig, DriftMonitor,
                       NullTelemetry, Telemetry, summarize_doc)
from repro.obs.report import main as report_main
from repro.runtime import (Dispatcher, DispatchPolicy, TuningCache,
                           default_registry, shape_bucket)
from repro.runtime.online import OnlineConfig, OnlineRefiner
from repro.runtime.registry import KernelRegistry, RegisteredKernel, Variant


# --------------------------------------------------------------------------
# fixtures: a two-variant toy kernel (near-free or sleeping variants)
# --------------------------------------------------------------------------

def _toy_registry(sleep_s=0.0):
    def abstract_params(a):
        return {"m": int(a.shape[0])}

    def call(args, p, sleep_s=sleep_s):
        if sleep_s:
            time.sleep(sleep_s)
        return jnp.asarray(args[0]) * 1.0

    flops = lambda p: float(p["m"])
    variants = tuple(
        Variant("toy", name, call, lambda p, _i=float(i): [p["m"], _i],
                flops)
        for i, name in enumerate(("v0", "v1")))
    reg = KernelRegistry()
    reg.register(RegisteredKernel(
        "toy", abstract_params, ("m", "variant"), variants,
        abstract_params=abstract_params,
        out_aval=lambda a: Aval(tuple(a.shape), a.dtype)))
    return reg


def _fitted_dispatcher(tmp_path, slowdown=1.0, sleep_s=0.0, telemetry=None):
    """Warm dispatcher over the toy kernel, fitted on buckets m=32..4096;
    v1 is ``slowdown`` x v0 (1.0 = a near-tie the gate must measure)."""
    reg = _toy_registry(sleep_s=sleep_s)
    d = Dispatcher(registry=reg,
                   cache=TuningCache(root=str(tmp_path / "tc")),
                   policy=DispatchPolicy(min_window=1e-4),
                   telemetry=telemetry)
    entry = d._entry("toy")
    for m in (32, 128, 512, 2048, 4096):
        rows = reg.feature_rows("toy", {"m": m})
        entry.add_rows(rows, [m / 1e6, slowdown * m / 1e6],
                       shape_bucket({"m": m}))
    entry.fit(model=LinearModel())
    return d


# --------------------------------------------------------------------------
# DriftMonitor
# --------------------------------------------------------------------------

def test_drift_monitor_flags_when_live_mape_leaves_band():
    mon = DriftMonitor(DriftConfig(min_obs=4, factor=2.0))
    for _ in range(4):
        mon.observe("bad", predicted_s=1.0, actual_s=2.0, fit_band_pct=10.0)
        mon.observe("good", predicted_s=1.0, actual_s=1.02,
                    fit_band_pct=10.0)
    assert mon.live_mape("bad") == pytest.approx(50.0)
    assert mon.flagged("bad") and not mon.flagged("good")
    assert mon.flags() == ["bad"]
    s = mon.status()
    assert s["bad"]["flagged"] and s["bad"]["n"] == 4
    assert s["bad"]["fit_band_pct"] == pytest.approx(10.0)


def test_drift_monitor_needs_min_obs_before_flagging():
    mon = DriftMonitor(DriftConfig(min_obs=8))
    for _ in range(7):
        mon.observe("k", 1.0, 10.0, fit_band_pct=1.0)   # 90% APE
    assert not mon.flagged("k")                          # 7 < min_obs
    mon.observe("k", 1.0, 10.0, fit_band_pct=1.0)
    assert mon.flagged("k")


def test_drift_monitor_band_defaults_and_follows_refits():
    mon = DriftMonitor(DriftConfig(default_band_pct=25.0))
    mon.observe("k", 1.0, 1.5)                  # no band reported
    assert mon.band("k") == pytest.approx(25.0)
    mon.observe("k", 1.0, 1.5, fit_band_pct=5.0)
    mon.observe("k", 1.0, 1.5)                  # None never clobbers
    assert mon.band("k") == pytest.approx(5.0)


def test_drift_monitor_json_roundtrip():
    mon = DriftMonitor(DriftConfig(min_obs=2, factor=3.0))
    for _ in range(3):
        mon.observe("k", 1.0, 2.0, fit_band_pct=4.0)
    again = DriftMonitor.from_json(mon.to_json())
    assert again.status() == mon.status()
    assert again.config == mon.config


# --------------------------------------------------------------------------
# Telemetry primitives + summary
# --------------------------------------------------------------------------

def test_telemetry_counters_histograms_series_events():
    tel = Telemetry(run_id="unit")
    tel.count("dispatch.predicted")
    tel.count("dispatch.predicted", 2)
    tel.gauge("exec.queue_depth.d0", 3.0)
    for v in (1.0, 2.0, 3.0, 4.0):
        tel.observe("dispatch.overhead_s", v)
    tel.instant("gate:toy", cat="gate", reason="near_tie")
    with tel.span("compile", cat="span"):
        pass
    s = tel.summary()
    assert s["run_id"] == "unit"
    assert s["decisions"] == {"dispatch.predicted": 3}
    h = s["histograms"]["dispatch.overhead_s"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == pytest.approx(2.5)
    assert s["events"] == {"gate": 1, "span": 1}
    assert s["series"] == ["exec.queue_depth.d0"]
    # the span measured a real (non-negative) duration on the shared clock
    span = tel.events(cat="span")[0]
    assert span["t1"] >= span["t0"] >= tel.epoch


def test_telemetry_residuals_feed_drift_and_mirror_a_series():
    tel = Telemetry(run_id="drift", drift=DriftConfig(min_obs=2))
    tel.residual("toy", predicted_s=1.0, actual_s=2.0, fit_band_pct=10.0)
    tel.residual("toy", predicted_s=1.0, actual_s=2.0)
    s = tel.summary()
    assert s["drift"]["toy"]["live_mape_pct"] == pytest.approx(50.0)
    assert s["drift_flags"] == ["toy"]
    series = tel.series("drift.live_mape.toy")
    assert [v for _, v in series] == pytest.approx([50.0, 50.0])


def test_telemetry_save_load_summary_identical(tmp_path):
    """summarize_doc is pure over the JSON document: the live summary and
    the one recomputed from the saved file must be equal."""
    tel = Telemetry(run_id="rt")
    tel.count("exec.steals", 2)
    tel.observe("kernel.toy.s", 0.002)
    tel.gauge("exec.queue_depth.d0", 1.0)
    tel.instant("steal:t", cat="steal", planned="d0", chosen="d1")
    tel.residual("toy", 1.0, 1.1, fit_band_pct=20.0)
    path = str(tmp_path / "tel.json")
    tel.save(path)
    assert summarize_doc(Telemetry.load(path)) == tel.summary()


def test_telemetry_save_is_atomic(tmp_path, monkeypatch):
    """A failed save never corrupts an existing file (temp + rename)."""
    path = str(tmp_path / "tel.json")
    tel = Telemetry(run_id="keep")
    tel.count("ok", 1)
    tel.save(path)
    before = open(path).read()
    bad = Telemetry(run_id="torn")
    monkeypatch.setattr(Telemetry, "to_json",
                        lambda self: (_ for _ in ()).throw(RuntimeError()))
    with pytest.raises(RuntimeError):
        bad.save(path)
    assert open(path).read() == before       # original intact
    monkeypatch.undo()
    bad.save(path)                            # and a clean retry lands
    assert Telemetry.load(path)["run_id"] == "torn"
    assert not (tmp_path / "tel.json.tmp").exists()


def test_telemetry_concurrent_writers_lose_nothing(tmp_path):
    """Stress the shared-state surfaces from many threads: counters sum
    exactly, every gauge/histogram/residual point lands, and concurrent
    ``to_json``/``save`` snapshots never crash or tear."""
    import threading

    tel = Telemetry(run_id="stress", drift=DriftConfig(min_obs=1))
    n_threads, n_iter = 8, 200
    errors = []

    def hammer(i):
        try:
            for j in range(n_iter):
                tel.count("shared.counter")
                tel.count(f"per.thread.{i}", 2)
                tel.gauge(f"gauge.{i}", float(j))
                tel.observe("hist.s", 1e-3 * (j + 1))
                tel.residual("stress", 1.0, 1.1, fit_band_pct=50.0)
                if j % 50 == 0:
                    tel.to_json()
                    tel.save(str(tmp_path / f"snap_{i}.json"))
        except BaseException as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    c = tel.counters()
    assert c["shared.counter"] == n_threads * n_iter
    for i in range(n_threads):
        assert c[f"per.thread.{i}"] == 2 * n_iter
        assert len(tel.series(f"gauge.{i}")) == n_iter
    doc = tel.to_json()
    assert doc["histograms"]["hist.s"]["count"] == n_threads * n_iter
    assert summarize_doc(doc)["drift"]["stress"]["n"] == n_threads * n_iter
    # the final save loads back as the same document shape
    tel.save(str(tmp_path / "final.json"))
    assert Telemetry.load(
        str(tmp_path / "final.json"))["run_id"] == "stress"


def test_null_telemetry_is_inert():
    NULL_TELEMETRY.count("x")
    NULL_TELEMETRY.gauge("g", 1.0)
    NULL_TELEMETRY.observe("h", 1.0)
    NULL_TELEMETRY.instant("i")
    NULL_TELEMETRY.residual("k", 1.0, 2.0)
    with NULL_TELEMETRY.span("s"):
        pass
    assert NULL_TELEMETRY.counters() == {}
    assert not NullTelemetry.enabled and Telemetry.enabled
    assert summarize_doc(NULL_TELEMETRY.to_json())["decisions"] == {}


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------

def test_report_cli_roundtrips_summary_json(tmp_path, capsys):
    tel = Telemetry(run_id="cli")
    tel.count("dispatch.predicted", 5)
    tel.observe("dispatch.overhead_s", 1e-5)
    tel.observe("kernel.toy.s", 1e-3)
    path = str(tmp_path / "tel.json")
    tel.save(path)
    assert report_main(["report", path, "--json"]) == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed == json.loads(json.dumps(tel.summary()))
    # text mode renders the same summary without crashing
    assert report_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "predicted=5" in out and "drift flags: none" in out


def test_report_cli_check_gates_on_drift(tmp_path):
    tel = Telemetry(run_id="drifty", drift=DriftConfig(min_obs=2))
    for _ in range(3):
        tel.residual("toy", 1.0, 10.0, fit_band_pct=5.0)   # 90% vs 5% band
    path = str(tmp_path / "tel.json")
    tel.save(path)
    assert report_main(["report", path, "--check"]) == 1
    # the saved monitor keeps raw windows: the factor is a read-time choice
    assert report_main(["report", path, "--check", "--factor", "50"]) == 0
    bogus = tmp_path / "bogus.json"
    bogus.write_text("{}")
    assert report_main(["report", str(bogus)]) == 2


# --------------------------------------------------------------------------
# trace exports: epoch sharing, Chrome schema, Gantt contract (satellites)
# --------------------------------------------------------------------------

def test_trace_epoch_first_caller_wins_and_rebases_exports():
    tr = ExecutionTrace()
    tr.set_epoch(100.0)
    tr.set_epoch(50.0)                       # ignored: first caller wins
    tr.record("a", "compute", "d0", 100.5, 101.0)
    tr.record("s", "steal", "d0", 100.7, 100.7, note="d0->d1")
    assert tr.t0 == 100.0
    doc = tr.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    ev = {e["name"]: e for e in doc["traceEvents"]}
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["d0"]
    assert ev["a"]["ph"] == "X" and ev["a"]["ts"] == pytest.approx(0.5e6)
    assert ev["a"]["dur"] == pytest.approx(0.5e6)
    assert ev["s"]["ph"] == "i" and ev["s"]["args"] == {"note": "d0->d1"}
    csv = tr.to_gantt_csv().splitlines()
    assert csv[0] == "task,kind,device,start_s,finish_s"
    task, kind, device, start, finish = csv[1].split(",")
    assert (task, kind, device) == ("a", "compute", "d0")
    assert float(start) == pytest.approx(0.5)
    assert float(finish) == pytest.approx(1.0)


def test_executor_pins_epoch_so_chrome_and_gantt_start_at_zero():
    tracer = ExecutionTrace()
    AsyncExecutor(tracer=tracer).run(
        [ExecTask("t", "d0", lambda env: time.sleep(0.01))])
    assert tracer.epoch is not None
    assert tracer.epoch <= min(e.begin_s for e in tracer.events)
    first = [e for e in tracer.to_chrome()["traceEvents"]
             if e["ph"] == "X"][0]
    assert first["ts"] >= 0.0
    assert float(tracer.to_gantt_csv().splitlines()[1].split(",")[3]) >= 0.0


def test_chrome_trace_merges_gate_steal_and_refit_on_one_clock(tmp_path):
    """The acceptance trace: gate rejections, a steal, and refits — fed by
    three different layers — land in ONE Chrome trace, with gauge series
    as counter tracks, all relative to the executor's epoch."""
    tel = Telemetry(run_id="merged")

    # (1) gate rejection: warm dispatcher, near-tie variants, unseen bucket
    d = _fitted_dispatcher(tmp_path, slowdown=1.0, telemetry=tel)
    d.dispatch("toy", jnp.ones((32768,), jnp.float32))
    assert tel.counters()["gate.reject"] == 1

    # (2) a steal: loaded planned lane, idle candidate
    tracer = ExecutionTrace()
    hog = ExecTask("hog", "d0", lambda env: time.sleep(0.1) or "hog",
                   predict=lambda dev: 0.1,
                   run_on=lambda env, dev: "hog", runnable_on=("d0",),
                   priority=0.0)
    work = ExecTask("work", "d0", lambda env: "work",
                    predict={"d0": 0.05, "d1": 0.06}.get,
                    run_on=lambda env, dev: "work",
                    runnable_on=("d0", "d1"), priority=1.0)
    AsyncExecutor(tracer=tracer, steal=StealPolicy(), telemetry=tel).run(
        [hog, work])

    # (3) refits: observations through the refiner over the same cache
    ref = OnlineRefiner(d.cache,
                        OnlineConfig(refit_every=1, model_factory=LinearModel,
                                     save=False), telemetry=tel)
    rows = d.registry.feature_rows("toy", {"m": 128})
    ref.observe("toy", rows[0], shape_bucket({"m": 128}), 130e-6,
                predicted_s=128e-6)

    events = tracer.to_chrome(telemetry=tel)["traceEvents"]
    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert "gate:toy" in instants
    assert "steal:work" in instants
    assert "refit:toy" in instants
    tracks = {e["name"] for e in events if e["ph"] == "C"}
    assert any(t.startswith("exec.queue_depth.") for t in tracks)
    # one time base: every merged event is relative to the executor epoch
    tids = {e["tid"] for e in events if e["ph"] == "M"}
    assert len(tids) == len({e.device for e in tracer.events}) + 1


# --------------------------------------------------------------------------
# dispatcher integration: counters, residuals, the <5% overhead criterion
# --------------------------------------------------------------------------

def test_dispatch_records_modes_memo_hits_and_residuals(tmp_path):
    tel = Telemetry(run_id="disp")
    d = _fitted_dispatcher(tmp_path, slowdown=10.0, telemetry=tel)
    a = jnp.ones((128,), jnp.float32)        # seen bucket: no gate
    d.dispatch("toy", a)                     # warm predicted (jit compiles)
    d.dispatch("toy", a)                     # memo hit: clean wall time
    c = tel.counters()
    assert c["dispatch.predicted"] == 2
    assert c["dispatch.memo_hit"] == 1
    s = tel.summary()
    assert s["histograms"]["dispatch.overhead_s"]["count"] == 2
    assert s["histograms"]["kernel.toy.s"]["count"] == 2
    # residuals only from the memo-hit execution (jit-compile rule)
    assert s["drift"]["toy"]["n"] == 1


def test_gate_outcomes_are_counted_and_explained(tmp_path):
    tel = Telemetry(run_id="gate")
    near = _fitted_dispatcher(tmp_path / "near", slowdown=1.0,
                              telemetry=tel)
    near.dispatch("toy", jnp.ones((32768,), jnp.float32))
    assert tel.counters()["gate.reject"] == 1
    assert tel.counters()["dispatch.gated"] == 1
    ev = tel.events(cat="gate")[0]
    assert ev["args"]["reason"] == "near_tie"
    # a rejection means the predicted spread sat inside the error band
    assert ev["args"]["spread_pct"] <= ev["args"]["band_pct"]

    clear = _fitted_dispatcher(tmp_path / "clear", slowdown=10.0,
                               telemetry=tel)
    clear.dispatch("toy", jnp.ones((32768,), jnp.float32))
    assert tel.counters()["gate.accept"] == 1


def test_steady_state_dispatch_overhead_under_5pct_with_telemetry(tmp_path):
    """The acceptance bound: telemetry attached, warm memoized dispatches,
    decision overhead below 5% of dispatch+kernel wall."""
    tel = Telemetry(run_id="overhead")
    d = _fitted_dispatcher(tmp_path, slowdown=2.0, sleep_s=0.005,
                           telemetry=tel)
    a = jnp.ones((128,), jnp.float32)
    d.dispatch("toy", a)                     # warm-up: jit + decision memo
    for _ in range(20):
        d.dispatch("toy", a)
    s = tel.summary()
    assert s["decisions"]["dispatch.memo_hit"] == 20
    assert s["overhead"]["dispatch_frac"] < 0.05


def test_telemetry_attaches_post_construction_and_reaches_refiner(tmp_path):
    d = _fitted_dispatcher(tmp_path, slowdown=10.0)
    d.policy = d.policy                      # no-op; keep the dispatcher
    tel = Telemetry(run_id="late")
    d.telemetry = tel                        # the bench's post-warmup attach
    assert d._telemetry is tel
    online = Dispatcher(registry=_toy_registry(),
                        cache=TuningCache(root=str(tmp_path / "tc2")),
                        policy=DispatchPolicy(online=True))
    online.telemetry = tel
    assert online.refiner.telemetry is tel


# --------------------------------------------------------------------------
# structural determinism: identical fresh sim runs, identical decisions
# --------------------------------------------------------------------------

def test_fixed_seed_sim_runs_summarize_identically(tmp_path):
    from repro.api import ops, trace
    from repro.runtime.simdev import fake_matmul_device

    def one_run(tag: str) -> dict:
        reg = default_registry(include=["matmul"])
        devs = {n: fake_matmul_device(str(tmp_path / tag), n, s, reg)
                for n, s in (("d0", 1.0e9), ("d1", 0.9e9))}
        rng = np.random.RandomState(0)
        a, b, w = (jnp.asarray(rng.rand(96, 96), jnp.float32)
                   for _ in range(3))
        with trace(registry=reg) as tb:
            x = ops.matmul(a, b)
            y = ops.matmul(x, w)
            ops.matmul(x, y)
        tel = Telemetry(run_id="det")
        c = tb.program.compile(devices=devs, bindings=dict(tb.bindings),
                               executor="async", telemetry=tel)
        c()
        return tel.summary()

    s1, s2 = one_run("runA"), one_run("runB")
    assert s1["decisions"] == s2["decisions"]
    assert s1["events"] == s2["events"]
    assert sorted(s1["drift"]) == sorted(s2["drift"])
    assert {n for n in s1["histograms"]} == {n for n in s2["histograms"]}


# --------------------------------------------------------------------------
# per-compile makespan + the bench/schema folding
# --------------------------------------------------------------------------

def test_compiled_program_records_predicted_vs_realized_makespan(tmp_path):
    from repro.api import ops, trace
    from repro.runtime.simdev import fake_matmul_device

    reg = default_registry(include=["matmul"])
    dev = fake_matmul_device(str(tmp_path / "dev"), "d0", 1.0e9, reg)
    rng = np.random.RandomState(0)
    a, b = (jnp.asarray(rng.rand(96, 96), jnp.float32) for _ in range(2))
    with trace(registry=reg) as tb:
        ops.matmul(a, b)
    tel = Telemetry(run_id="makespan")
    c = tb.program.compile(devices={"d0": dev},
                           bindings=dict(tb.bindings), telemetry=tel)
    c()
    ev = tel.events(cat="makespan")
    assert len(ev) == 1
    args = ev[0]["args"]
    assert args["predicted_s"] == pytest.approx(c.makespan)
    assert args["realized_s"] > 0 and args["ape_pct"] >= 0
    assert tel.summary()["histograms"]["program.wall_s"]["count"] == 1


def _min_bench_doc() -> dict:
    mode_f = {"best": 1.0, "default": 2.0, "worst": 3.0}
    return {
        "schema": 3, "quick": True, "generated_unix": 1.0,
        "host_fingerprint": {"platform": "test"},
        "configs": {"cpu": {"kind": "real", "executor": "sequential",
                            "devices": ["local"],
                            "device_mape": {"local": {
                                "toy": {"mape_pct": 3.0, "n_rows": 10}}}}},
        "workloads": {"w": {
            "size": "small", "kernels": ["toy"], "n_nodes": 2,
            "configs": {"cpu": {
                "n_transfers": 0, "wall_s": dict(mode_f),
                "predicted_makespan_s": dict(mode_f),
                "speedup_vs_default": 2.0, "speedup_vs_worst": 3.0,
                "overhead": {"dispatch_frac": 0.01, "executor_frac": 0.1},
                "mape": {"toy": 3.0},
                "telemetry": {
                    "decisions": {"dispatch.predicted": 4},
                    "overhead": {"dispatch_frac": 0.01},
                    "drift": {"toy": {"live_mape_pct": 4.0,
                                      "fit_band_pct": 3.0, "n": 4,
                                      "flagged": False}},
                    "drift_flags": []}}}}},
        "geomean": {"cpu": {"speedup_vs_default": 2.0,
                            "speedup_vs_worst": 3.0}},
        "external": {},
    }


def test_bench_schema3_validates_and_gates_telemetry():
    from repro.bench.schema import validate_bench

    doc = _min_bench_doc()
    assert validate_bench(doc) is doc
    stale = _min_bench_doc()
    stale["schema"] = 2                      # telemetry needs schema >= 3
    with pytest.raises(ValueError, match="schema >= 3"):
        validate_bench(stale)
    bad = _min_bench_doc()
    bad["workloads"]["w"]["configs"]["cpu"]["telemetry"]["drift_flags"] = [1]
    with pytest.raises(ValueError, match="drift_flags"):
        validate_bench(bad)


def test_bench_history_rows_tolerate_schemas_and_junk(tmp_path):
    from repro.bench.history import format_history, load_row

    p3 = tmp_path / "bench.json"
    doc = _min_bench_doc()
    doc["workloads"]["w"]["configs"]["cpu"]["telemetry"]["drift_flags"] = \
        ["toy"]
    doc["adaptive"] = {"geomean_speedup_vs_static": 1.25}
    p3.write_text(json.dumps(doc))
    row = load_row(str(p3))
    assert row["schema"] == 3 and row["drift_flags"] == ["cpu:toy"]
    assert row["adaptive_geomean"] == pytest.approx(1.25)
    assert row["geomean_vs_default"] == {"cpu": 2.0}

    v1 = _min_bench_doc()
    v1["schema"] = 1
    del v1["workloads"]["w"]["configs"]["cpu"]["telemetry"]
    p1 = tmp_path / "bench_v1.json"
    p1.write_text(json.dumps(v1))
    old = load_row(str(p1))
    assert old["schema"] == 1 and old["drift_flags"] == []

    junk = tmp_path / "junk.json"
    junk.write_text("not json")
    assert "error" in load_row(str(junk))
    lines = format_history([row, old, load_row(str(junk))])
    assert any("drift: cpu:toy" in ln for ln in lines)
    assert "adapt" in lines[0] and "-- Expecting value" in lines[-1]


# --------------------------------------------------------------------------
# end to end: the bench adaptive scenario saves a merged trace + telemetry
# --------------------------------------------------------------------------

def test_run_adaptive_saves_merged_trace_and_telemetry(tmp_path):
    from repro.bench.harness import run_adaptive

    section = run_adaptive(quick=True, results_dir=str(tmp_path / "res"),
                           device_root=str(tmp_path / "devs"),
                           workloads=["mixed_dag"], size="small")
    doc = json.load(open(section["trace_path"]))
    events = doc["traceEvents"]
    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert any(n.startswith("steal:") for n in instants)
    assert any(n.startswith("refit:") for n in instants)
    tracks = {e["name"] for e in events if e["ph"] == "C"}
    assert any(t.startswith("exec.queue_depth.") for t in tracks)
    assert any(t.startswith("drift.live_mape.") for t in tracks)

    tel_doc = Telemetry.load(section["telemetry_path"])
    s = summarize_doc(tel_doc)
    w = section["workloads"]["mixed_dag"]
    assert s["decisions"]["online.refits"] > 0
    assert s["decisions"]["exec.steals"] == w["n_steals"]
    assert s["drift"]                        # residuals flowed end to end
    # the report CLI renders the same file (exit 0 or 1: drift flags are a
    # legitimate outcome of the mis-seeded scenario, not a failure here)
    assert report_main(["report", section["telemetry_path"],
                        "--check"]) in (0, 1)


# --------------------------------------------------------------------------
# spans on the profiler's trace; the compile counter
# --------------------------------------------------------------------------

def test_dispatch_span_holds_decide_launch_wait_in_order(tmp_path):
    from _trace import host_spans, profiled

    d = _fitted_dispatcher(tmp_path, slowdown=10.0)
    a = jnp.ones((128,), jnp.float32)
    d.dispatch("toy", a)                     # jit compiles outside the trace
    with profiled(tmp_path / "trace"):
        d.dispatch("toy", a)
    spans = host_spans(tmp_path / "trace", ("dispatch.",))
    assert [s.name for s in spans] == ["dispatch.toy", "dispatch.decide",
                                       "dispatch.launch", "dispatch.wait"]
    outer, decide, launch, wait = spans
    assert all(outer.holds(s) for s in (decide, launch, wait))
    assert decide.end <= launch.start and launch.end <= wait.start
    sel = d.selections[-1]
    # the dispatcher's own record of the same phases, on perf_counter
    assert 0 < sel.overhead_s and sel.launched_at <= sel.done_at
    assert sel.kernel_s >= sel.done_at - sel.launched_at


def test_telemetry_spans_reach_the_profiler_trace(tmp_path):
    from _trace import host_spans, profiled

    tel = Telemetry()
    with profiled(tmp_path / "trace"):
        with tel.span("obs.recorded"):
            pass
        with NULL_TELEMETRY.span("obs.null"):
            pass
    names = [s.name for s in host_spans(tmp_path / "trace", ("obs.",))]
    assert names == ["obs.recorded", "obs.null"]
    assert [e["name"] for e in tel.events()] == ["obs.recorded"]


def test_compile_counter_rises_on_a_new_shape_not_on_a_repeat():
    import jax

    from repro.obs import compile_counter

    counter = compile_counter()
    assert compile_counter() is counter      # one listener per process
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones((7, 3), jnp.float32)
    before = dict(counter.counts)
    t0 = time.perf_counter()
    f(x).block_until_ready()
    after = dict(counter.counts)
    assert after["compile"] == before["compile"] + 1
    assert after["trace"] > before["trace"]
    assert after["lower"] > before["lower"]
    assert counter.compiles_between(t0, time.perf_counter()) == 1
    busy = counter.busy_s()
    assert counter.busy_s(until=t0) < busy
    f(x).block_until_ready()
    assert counter.counts == after
    assert counter.busy_s() == busy
