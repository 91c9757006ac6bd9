"""repro.obs.explain: causal critical-path analysis and attribution.

Tier-1 coverage: the makespan partition invariant (bucket costs sum to
the realized makespan within 1%), deterministic analysis of a saved
trace (identical critical path / slack / attribution ranking across two
analyses), Chrome flow events for every dependency edge and the
``from_chrome`` round-trip, per-lane busy/wait/idle utilization, the
mis-seeded scenario naming the lying device's kernel as the top
misprediction contributor, serve TTFT waterfalls with < 5% residual,
the schema-5 ``attribution`` validator, and the ``obs explain`` CLI.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import compile_program, ops, trace
from repro.bench.schema import _validate_attribution
from repro.exec import CommModel, ExecutionTrace
from repro.obs.explain import (analyze_chrome, analyze_trace,
                               summarize_attribution,
                               waterfalls_from_telemetry)
from repro.obs.telemetry import Telemetry
from repro.runtime import TuningCache, default_registry, seed_from_programs
from repro.runtime.simdev import (SimLink, SkewedSimDispatcher,
                                  fake_matmul_device, true_time_at)

N = 160


# --------------------------------------------------------------------------
# fixtures
# --------------------------------------------------------------------------

def _three_matmuls(reg):
    rng = np.random.RandomState(0)
    a, b, w = (jnp.asarray(rng.rand(N, N), jnp.float32) for _ in range(3))
    with trace(registry=reg) as tb:
        x = ops.matmul(a, b)
        y = ops.matmul(x, w)
        ops.matmul(x, y)
    return tb.program, dict(tb.bindings)


def _diamond(reg):
    """Two independent matmuls feeding a third: EFT spreads the
    parallel pair across both devices, forcing a cross-device
    transfer into the trace."""
    rng = np.random.RandomState(1)
    a, b, c, d = (jnp.asarray(rng.rand(N, N), jnp.float32)
                  for _ in range(4))
    with trace(registry=reg) as tb:
        x = ops.matmul(a, b)
        y = ops.matmul(c, d)
        ops.matmul(x, y)
    return tb.program, dict(tb.bindings)


def _sim_run(tmp_path):
    """A two-device simulate-time run with transfers; returns the
    executed CompiledProgram (its ``last_trace`` is the subject)."""
    reg = default_registry(include=["matmul"])
    devs = {
        "d0": fake_matmul_device(str(tmp_path / "devs"), "d0", 1.0e9, reg,
                                 simulate_time=True),
        "d1": fake_matmul_device(str(tmp_path / "devs"), "d1", 0.9e9, reg,
                                 simulate_time=True),
    }
    link = SimLink(latency_s=2e-4, bytes_per_s=2e9)
    comm = CommModel(TuningCache(root=str(tmp_path / "comm")))
    link.measure_into(comm, (("d0", "d1"), ("d1", "d0")))
    prog, bindings = _diamond(reg)
    c = compile_program(prog, devices=devs, bindings=bindings,
                        executor="async", comm=comm,
                        transfer=link.transfer)
    c()
    return c


def _misseeded_run(tmp_path):
    """The PR's acceptance scenario: d0's cache claims 10x its true
    speed, d1 is honest, the async executor replays the mis-predicted
    EFT schedule verbatim — d0's kernel must surface as the top
    misprediction contributor."""
    from repro.runtime import Dispatcher, Fingerprint
    reg = default_registry(include=["matmul"])
    prog, bindings = _three_matmuls(reg)
    claimed = {"d0": 1.0e10, "d1": 1.0e9}    # d0 lies 10x; true rate 1e9
    true_time = true_time_at(reg, 1.0e9)
    devs = {}
    for name, rate in claimed.items():
        fp = Fingerprint("sim", f"explain-{name}", 1, 1, ("float32",))
        cache = TuningCache(root=str(tmp_path / "mis"), fingerprint=fp)
        seed_from_programs(Dispatcher(registry=reg, cache=cache), [prog],
                           rate, amplitude=1.0, reset=True)
        devs[name] = SkewedSimDispatcher(registry=reg, cache=cache,
                                         true_time=true_time)
    link = SimLink(latency_s=2e-4, bytes_per_s=2e9)
    comm = CommModel(TuningCache(root=str(tmp_path / "mis-comm")))
    link.measure_into(comm, (("d0", "d1"), ("d1", "d0")))
    c = compile_program(prog, devices=devs, bindings=bindings,
                        executor="async", comm=comm,
                        transfer=link.transfer)
    c()
    return c


# --------------------------------------------------------------------------
# the partition invariant + realized critical path
# --------------------------------------------------------------------------

def test_buckets_sum_to_makespan_within_1pct(tmp_path):
    c = _sim_run(tmp_path)
    doc = analyze_trace(c.last_trace)
    assert not doc.get("empty")
    assert doc["makespan_s"] > 0
    assert doc["residual_frac"] < 0.01
    assert abs(sum(doc["buckets"].values()) - doc["makespan_s"]) \
        <= 0.01 * doc["makespan_s"]
    assert doc["top_bottleneck"] in doc["buckets"]
    # the chain is contiguous: each link becomes ready when the previous
    # one ends, and the last link ends at the makespan
    cp = doc["critical_path"]
    assert cp[-1]["end_s"] == pytest.approx(doc["makespan_s"])
    for prev, cur in zip(cp, cp[1:]):
        assert cur["ready_s"] == pytest.approx(prev["end_s"])
    # every link's own split covers its segment
    for row in cp:
        seg = row["end_s"] - row["ready_s"]
        assert row["run_s"] + row["queue_s"] + row["overhead_s"] \
            == pytest.approx(seg, abs=1e-9)
    # slack: never negative, and zero for the chain's final task
    assert all(s >= 0.0 for s in doc["slack_s"].values())
    assert doc["slack_s"][cp[-1]["task"]] == pytest.approx(0.0, abs=1e-12)
    assert c.explain()["makespan_s"] == pytest.approx(doc["makespan_s"])


def test_lane_utilization_fractions(tmp_path):
    c = _sim_run(tmp_path)
    doc = analyze_trace(c.last_trace)
    lanes = doc["lanes"]
    assert set(lanes) >= {"d0", "d1"}
    for u in lanes.values():
        assert u["n_tasks"] >= 1
        for k in ("busy_frac", "wait_frac", "idle_frac"):
            assert 0.0 <= u[k] <= 1.0 + 1e-9
        assert u["busy_frac"] + u["wait_frac"] + u["idle_frac"] \
            == pytest.approx(1.0, abs=1e-6)


# --------------------------------------------------------------------------
# determinism + the saved-trace round trip
# --------------------------------------------------------------------------

def test_saved_trace_analysis_is_deterministic(tmp_path):
    c = _sim_run(tmp_path)
    path = tmp_path / "trace.json"
    c.last_trace.save_chrome(str(path))
    with open(path) as f:
        saved = json.load(f)
    a = analyze_chrome(saved)
    b = analyze_chrome(json.loads(json.dumps(saved)))
    # identical critical path, slack values, and attribution ranking —
    # byte-identical documents, not merely approximately equal
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_from_chrome_roundtrip_matches_live_analysis(tmp_path):
    c = _sim_run(tmp_path)
    live = analyze_trace(c.last_trace)
    saved = analyze_chrome(c.last_trace.to_chrome())
    assert [r["task"] for r in saved["critical_path"]] \
        == [r["task"] for r in live["critical_path"]]
    assert set(saved["buckets"]) == set(live["buckets"])
    # Chrome timestamps are microseconds: round-tripping costs < 1us/task
    assert saved["makespan_s"] == pytest.approx(live["makespan_s"],
                                                abs=1e-4)
    assert [(g["kernel"], g["shape_bucket"])
            for g in saved["mispredictions"]] \
        == [(g["kernel"], g["shape_bucket"])
            for g in live["mispredictions"]]


def test_chrome_flow_events_cover_every_dep_edge(tmp_path):
    c = _sim_run(tmp_path)
    doc = c.last_trace.to_chrome()
    evs = doc["traceEvents"]
    spans = {e["name"] for e in evs if e.get("ph") == "X"}
    n_edges = sum(len((e.get("args") or {}).get("deps", ()))
                  for e in evs if e.get("ph") == "X")
    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert n_edges > 0
    assert len(starts) == len(finishes) == n_edges
    assert all(e["cat"] == "flow" for e in starts + finishes)
    assert all(e.get("bp") == "e" for e in finishes)
    # ids pair one start with one finish
    assert sorted(e["id"] for e in starts) \
        == sorted(e["id"] for e in finishes)
    # deps/meta survive in args for every task span
    metas = [e for e in evs if e.get("ph") == "X"
             and (e.get("args") or {}).get("meta")]
    assert metas and all(m["args"]["meta"].get("kernel") for m in metas)
    assert spans  # the dep sources all exist as spans


# --------------------------------------------------------------------------
# misprediction attribution: the mis-seeded device is named
# --------------------------------------------------------------------------

def test_misseeded_device_kernel_tops_misprediction_ranking(tmp_path):
    c = _misseeded_run(tmp_path)
    doc = analyze_trace(c.last_trace)
    assert doc["residual_frac"] < 0.01
    mis = doc["mispredictions"]
    assert mis, "mis-seeded run must produce misprediction groups"
    top = mis[0]
    assert top["kernel"] == "matmul"
    assert "d0" in top["lanes"]
    assert top["cost_s"] > 0
    # d0 claimed 10x its true speed: the chain ran ~10x the prediction
    assert top["ape_pct"] > 100.0
    # the seeded fit is near-exact, so the live error leaves the band
    assert top["exceeds_fit_band"] is True
    # predicted-vs-realized path diff is reported (identical here is fine
    # — both chains run the same dependent matmul spine)
    assert doc["predicted"] is not None
    assert doc["predicted"]["path"]
    assert doc["divergence"] is not None


def test_summarize_attribution_passes_schema5_validator(tmp_path):
    c = _misseeded_run(tmp_path)
    att = summarize_attribution(analyze_trace(c.last_trace))
    _validate_attribution(att, "$.test.attribution")      # must not raise
    assert att["top_misprediction"]["kernel"] == "matmul"
    assert att["top_bottleneck"] in att["buckets"]
    bad = dict(att, top_bottleneck="nope")
    with pytest.raises(ValueError, match="top_bottleneck"):
        _validate_attribution(bad, "$.test.attribution")
    with pytest.raises(ValueError, match="buckets"):
        _validate_attribution(dict(att, buckets={}), "$.t")


# --------------------------------------------------------------------------
# serve waterfalls
# --------------------------------------------------------------------------

def test_serve_waterfalls_decompose_ttft(tmp_path):
    from repro.configs import ARCHS
    from repro.core.nnc import LinearModel
    from repro.models import build_model
    from repro.serve import (ServeEngine, fit_cost_entries,
                             record_decode_time, record_prefill_time)
    from repro.serve.request import ServeRequest

    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = TuningCache(root=str(tmp_path / "tc"))
    for p in (2, 4, 8, 16, 32):
        record_prefill_time(cache, p, p, 1e-4 * p * p)
    for ctx in (4, 8, 16, 32, 64):
        record_decode_time(cache, ctx, 1e-5 * ctx)
    fit_cost_entries(cache, model_factory=LinearModel, save=False)

    tel = Telemetry()
    eng = ServeEngine(model, cache, params=params, max_slots=2,
                      max_seq=96, admission="sjf", telemetry=tel,
                      record_rows=False)
    reqs = [ServeRequest(rid=i, prompt=[1 + i] * (2 + i), max_new=3 + i)
            for i in range(4)]
    stats = eng.run_trace(reqs)
    assert stats["completed"] == 4

    wf = waterfalls_from_telemetry(tel.to_json())
    assert wf["n_requests"] == 4
    assert wf["max_residual_frac"] < 0.05
    for rid, row in wf["requests"].items():
        parts = (row["queue_wait_s"] + row["prefill_s"] + row["decode_s"]
                 + row["sched_overhead_s"] + row["residual_s"])
        assert parts == pytest.approx(row["ttft_s"], abs=1e-9)
        assert row["prefill_s"] > 0      # every request consumed a prompt
        assert row["ttft_s"] > 0 and row["total_s"] >= row["ttft_s"]
        assert row["tokens"] == 3 + rid
    # the per-step spans carry (rid, slot, phase) for every active slot;
    # each prompt went in with a prefill span of its own
    steps = tel.events(cat="serve.step")
    assert len(steps) == stats["engine_steps"]
    prefills = [x for e in tel.events(cat="serve.prefill")
                for x in e["args"]["requests"]]
    assert sorted(x["rid"] for x in prefills) == [0, 1, 2, 3]
    assert all(x["phase"] == "prefill" for x in prefills)
    assert any(x["phase"] == "decode"
               for e in steps for x in e["args"]["requests"])


def test_telemetry_event_api_records_explicit_spans():
    tel = Telemetry()
    tel.event("x", 1.0, 2.5, cat="serve.step", step=7)
    (e,) = tel.events(cat="serve.step")
    assert (e["t0"], e["t1"], e["ph"]) == (1.0, 2.5, "span")
    assert e["args"] == {"step": 7}
    from repro.obs.telemetry import NULL_TELEMETRY
    NULL_TELEMETRY.event("x", 0.0, 1.0)          # no-op, must not raise
    assert NULL_TELEMETRY.events() == []


# --------------------------------------------------------------------------
# the CLI
# --------------------------------------------------------------------------

def test_explain_cli(tmp_path, capsys):
    from repro.obs.report import main
    c = _misseeded_run(tmp_path)
    trace_path = tmp_path / "exec_trace.json"
    c.last_trace.save_chrome(str(trace_path))

    out_path = tmp_path / "explain.json"
    assert main(["explain", str(trace_path), "--json",
                 "-o", str(out_path)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert str(trace_path) in doc["traces"]
    with open(out_path) as f:
        assert json.load(f) == doc

    # the mis-seeded run's top misprediction exceeds its fit band: the
    # CI hook exits 1 (non-blocking ::warning:: upstream)
    assert main(["explain", str(trace_path), "--check-band"]) == 1
    assert "FIT-BAND EXCEEDED" in capsys.readouterr().out

    bad = tmp_path / "not_a_trace.json"
    bad.write_text("{\"neither\": true}")
    assert main(["explain", str(bad)]) == 2


def test_report_trace_lane_utilization(tmp_path, capsys):
    from repro.obs.report import main
    c = _sim_run(tmp_path)
    trace_path = tmp_path / "exec_trace.json"
    c.last_trace.save_chrome(str(trace_path))
    tel_path = tmp_path / "telemetry.json"
    Telemetry(run_id="t").save(str(tel_path))
    assert main(["report", str(tel_path), "--trace",
                 str(trace_path)]) == 0
    out = capsys.readouterr().out
    assert "lane utilization" in out
    assert "d0" in out and "d1" in out


def test_analyze_empty_trace_is_explicit():
    doc = analyze_trace(ExecutionTrace(epoch=0.0))
    assert doc["empty"] and doc["makespan_s"] == 0.0
    assert doc["buckets"] == {} and doc["top_bottleneck"] is None
