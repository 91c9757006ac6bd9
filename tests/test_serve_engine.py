"""ServeEngine: predictor-driven continuous-batching serving.

Tier-1 coverage: seeded arrival traces, the typed ``ColdCacheError`` +
FIFO fallback, SJF admission ordering under a fitted split cost model,
batch-assembly invariants, bit-exact engine output against the
unbatched sequential reference, the prefill/decode row split (recording,
migration round-trip, distinct MAPE bands, reload determinism), per-slot
recurrent-state resets, the single-device ``stream_kv`` path, the
bounded queue, the ``repro.obs`` telemetry contract, and the schema-4
``serve`` bench section.  The 4-device ring-decode parity check runs in
a subprocess (XLA_FLAGS must precede the jax import) and is slow-marked.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.nnc import LinearModel
from repro.models import build_model
from repro.obs.telemetry import Telemetry
from repro.runtime.cache import TuningCache
from repro.serve import (ColdCacheError, ContinuousBatcher, ServeEngine,
                         bursty_trace, cost_model_from_cache,
                         fit_cost_entries, migrate_whole_request_rows,
                         poisson_trace, record_decode_time,
                         record_prefill_time, split_cost_model_from_cache)
from repro.serve.policy import (DECODE_STEP_KERNEL, PREFILL_STEP_KERNEL,
                                sjf_order)
from repro.serve.request import ServeRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _synthetic_fitted_cache(root, *, prefill_scale=1e-4, decode_scale=1e-5,
                            noise=0.0, seed=0) -> TuningCache:
    """A warm cache whose fitted times are proportional to the analytic
    work: prefill ~ prompt*ctx, decode ~ ctx."""
    rng = np.random.RandomState(seed)
    cache = TuningCache(root=str(root))
    for p in (2, 4, 8, 16, 32):
        jitter = 1.0 + noise * rng.randn()
        record_prefill_time(cache, p, p, prefill_scale * p * p * jitter)
    for ctx in (4, 8, 16, 32, 64):
        jitter = 1.0 + noise * rng.randn()
        record_decode_time(cache, ctx, decode_scale * ctx * jitter)
    fit_cost_entries(cache, model_factory=LinearModel, save=False)
    return cache


def _trace_key(reqs):
    return [(r.rid, tuple(r.prompt), r.max_new, r.arrival_step)
            for r in reqs]


# --------------------------------------------------------------------------
# arrival traces
# --------------------------------------------------------------------------

def test_trace_generators_deterministic():
    assert _trace_key(poisson_trace(8, seed=3)) == \
        _trace_key(poisson_trace(8, seed=3))
    assert _trace_key(bursty_trace(3, seed=5)) == \
        _trace_key(bursty_trace(3, seed=5))
    assert _trace_key(poisson_trace(8, seed=3)) != \
        _trace_key(poisson_trace(8, seed=4))
    # arrivals are ordered and bursts land shorts + longs on the same step
    pois = poisson_trace(16, seed=1)
    assert all(a.arrival_step <= b.arrival_step
               for a, b in zip(pois, pois[1:]))
    burst = bursty_trace(2, seed=0, burst_gap=24)
    steps = {r.arrival_step for r in burst}
    assert steps == {0, 24}
    for step in steps:
        lens = sorted(len(r.prompt) for r in burst
                      if r.arrival_step == step)
        assert lens == [2, 2, 2, 24]


# --------------------------------------------------------------------------
# typed cold-cache error + split cost model
# --------------------------------------------------------------------------

def test_cold_cache_error_is_typed(tmp_path):
    cache = TuningCache(root=str(tmp_path / "tc"))
    with pytest.raises(ColdCacheError) as ei:
        cost_model_from_cache(cache)
    assert isinstance(ei.value, ValueError)          # old callers survive
    assert set(ei.value.kernels) == {PREFILL_STEP_KERNEL,
                                     DECODE_STEP_KERNEL}
    # rows alone are not enough — the *fitted model* is what SJF needs
    record_prefill_time(cache, 4, 4, 1e-3)
    record_decode_time(cache, 8, 1e-4)
    with pytest.raises(ColdCacheError):
        split_cost_model_from_cache(cache)


def test_split_model_predicts_ttft_and_request_time(tmp_path):
    cache = _synthetic_fitted_cache(tmp_path / "tc")
    m = split_cost_model_from_cache(cache)
    # prefill is superlinear in prompt, decode linear in context
    assert m.prefill_seconds(2) < m.prefill_seconds(8) \
        < m.prefill_seconds(32)
    assert m.decode_seconds_per_token(4) < m.decode_seconds_per_token(32)
    # whole-request composition orders short before long
    assert m.request_seconds(2, 4) < m.request_seconds(8, 8) \
        < m.request_seconds(24, 16)
    # the callable contract of the pre-split cost model still holds
    assert m(2, 4) == m.request_seconds(2, 4)
    reqs = [ServeRequest(rid=0, prompt=[1] * 24, max_new=16),
            ServeRequest(rid=1, prompt=[1] * 2, max_new=4)]
    assert [r.rid for r in sjf_order(reqs, m)] == [1, 0]


def test_split_fits_have_distinct_mape_bands(tmp_path):
    cache = _synthetic_fitted_cache(tmp_path / "tc", noise=0.2, seed=7)
    prefill = cache.entry(PREFILL_STEP_KERNEL)
    decode = cache.entry(DECODE_STEP_KERNEL)
    assert prefill.fit_mape is not None and decode.fit_mape is not None
    # two separate fits over different noise draws: the error bands are
    # per-kernel, not one shared whole-request band
    assert prefill.fit_mape != decode.fit_mape
    m = split_cost_model_from_cache(cache)
    assert m.fit_band_pct == max(prefill.fit_mape, decode.fit_mape)


def test_whole_request_row_migration_roundtrip(tmp_path):
    # build a pre-split cache: whole-request rows under decode_step with
    # the old (prompt, new) layout and y ~ prefill + per-token decode
    cache = TuningCache(root=str(tmp_path / "tc"))
    old = cache.entry(DECODE_STEP_KERNEL,
                      feature_names=["prompt", "new"],
                      variant_names=["engine"])
    shapes = [(2, 4), (4, 4), (8, 8), (16, 8), (32, 16), (24, 16)]
    true_s = {}
    for p, n in shapes:
        # per-op-uniform timing — exactly what the old c = (p+n)^2 layout
        # asserted about these rows, so the split preserves it
        t = 2e-5 * (p + n) ** 2
        true_s[(p, n)] = t
        old.add_rows(np.asarray([[float(p), float(n),
                                  float((p + n) ** 2)]]), [t],
                     bucket=(("new", n), ("prompt", p)))
    cache.save()

    fresh = TuningCache(root=str(tmp_path / "tc"))
    assert migrate_whole_request_rows(fresh) == len(shapes)
    assert migrate_whole_request_rows(fresh) == 0        # idempotent
    # the stale layout is gone: the entry now has the split features
    assert fresh.entry(DECODE_STEP_KERNEL).feature_names == ["ctx"]
    m = fit_cost_entries(fresh, model_factory=LinearModel)
    # the migrated signal survives: every shape within the ridge model's
    # band (the regularized log-space fit trades exactness for stability)
    for (p, n), t in true_s.items():
        pred = m.request_seconds(p, n)
        assert abs(pred - t) / t < 0.5, (p, n, pred, t)
    # ...and the whole-request ordering the old model gave survives
    assert m.request_seconds(2, 4) < m.request_seconds(4, 4) \
        < m.request_seconds(16, 8) < m.request_seconds(24, 16)


def test_tunecache_reload_keeps_admission_order(tmp_path, tiny_model):
    model, params = tiny_model
    _synthetic_fitted_cache(tmp_path / "tc").save()

    def admitted_first():
        cache = TuningCache(root=str(tmp_path / "tc"))
        eng = ServeEngine(model, cache, params=params, max_slots=1,
                          max_seq=64, admission="sjf", record_rows=False)
        assert eng.policy_name == "sjf"
        eng.submit(ServeRequest(rid=0, prompt=[1] * 10, max_new=3))
        eng.submit(ServeRequest(rid=1, prompt=[1] * 2, max_new=3))
        eng.submit(ServeRequest(rid=2, prompt=[1] * 5, max_new=3))
        eng.step()
        return eng.slots[0].rid, [r.rid for r in eng.queue]

    # two engines over two *reloads* of the same fitted cache must order
    # admissions identically (the determinism CI's serve step relies on)
    assert admitted_first() == admitted_first() == (1, [2, 0])


# --------------------------------------------------------------------------
# engine: admission, fallback, assembly, exactness
# --------------------------------------------------------------------------

def test_cold_cache_falls_back_to_fifo_and_still_serves(tmp_path,
                                                        tiny_model):
    model, params = tiny_model
    tel = Telemetry()
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=2, max_seq=64,
                      admission="sjf", telemetry=tel)
    assert eng.requested_policy == "sjf"
    assert eng.policy_name == "fifo"
    assert tel.counters()["serve.admission_fallback"] == 1
    reqs = [ServeRequest(rid=i, prompt=[1 + i] * 3, max_new=3)
            for i in range(3)]
    stats = eng.run_trace(reqs)
    assert stats["completed"] == 3 and stats["admission_fallback"]
    # FIFO: admission order is arrival order
    admits = tel.events(cat="admission")
    assert [e["args"]["rid"] for e in admits] == [0, 1, 2]
    assert all(e["args"]["policy"] == "fifo" for e in admits)


def test_sjf_admission_orders_queue_under_fitted_model(tmp_path,
                                                       tiny_model):
    model, params = tiny_model
    cache = _synthetic_fitted_cache(tmp_path / "tc")
    eng = ServeEngine(model, cache, params=params, max_slots=1,
                      max_seq=64, admission="sjf", record_rows=False)
    assert eng.policy_name == "sjf"
    long_req = ServeRequest(rid=0, prompt=[1] * 10, max_new=3)
    short_req = ServeRequest(rid=1, prompt=[1] * 2, max_new=3)
    eng.submit(long_req)
    eng.submit(short_req)
    eng.step()
    assert eng.slots[0] is short_req
    assert short_req.predicted_s is not None
    assert long_req.predicted_s > short_req.predicted_s


def test_batch_assembly_invariants(tmp_path, tiny_model):
    model, params = tiny_model
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=2, max_seq=96,
                      admission="fifo")
    reqs = poisson_trace(6, seed=2)
    seen_slots = set()
    pending = list(reqs)
    for r in pending:
        r.arrival_step = 0
    for r in pending:
        eng.submit(r)
    while eng.step():
        active = [s for s in eng.slots if s is not None]
        assert len(active) <= eng.max_slots
        assert all(eng.prompt_left[i] >= 0 for i in range(eng.max_slots))
        # a slot's admission index never exceeds the shared cache index
        for i, s in enumerate(eng.slots):
            if s is not None:
                assert eng.start[i] <= eng.index
                seen_slots.add(i)
    assert all(r.done and len(r.generated) == r.max_new for r in reqs)
    assert all(r.slot in range(eng.max_slots) for r in reqs)
    assert seen_slots == {0, 1}                  # both slots actually used


def test_engine_matches_unbatched_sequential_reference(tmp_path,
                                                       tiny_model):
    """Bit-exactness: the compiled-program execution path and slot
    machinery must not perturb a single token vs running each request
    alone through the plain batcher."""
    model, params = tiny_model

    def mk():
        rng = np.random.RandomState(0)
        return [ServeRequest(
            rid=i, prompt=[int(t) for t in rng.randint(1, 256, size=n)],
            max_new=4) for i, n in enumerate([4, 7, 3, 5])]

    reqs = mk()
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=2, max_seq=64,
                      admission="fifo")
    stats = eng.run_trace(reqs)
    assert stats["completed"] == len(reqs)

    for ref_req, got in zip(mk(), reqs):
        solo = ContinuousBatcher(model, params, max_slots=1, max_seq=64)
        solo.submit(ref_req)
        solo.run()
        assert got.generated == ref_req.generated, got.rid


def test_recurrent_slot_reset_matches_fresh_engine(tmp_path):
    """A freshly admitted slot on a recurrent (xLSTM) config must behave
    exactly like a fresh engine: the previous tenant's mlstm/slstm state
    is zeroed on admission (KV has positional masking, recurrence does
    not)."""
    cfg = dataclasses.replace(ARCHS["xlstm-1.3b"].reduced(),
                              layer_pattern=("mlstm", "slstm"), n_layers=2,
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [7, 3, 11, 5]

    eng = ContinuousBatcher(model, params, max_slots=1, max_seq=64)
    first = ServeRequest(rid=0, prompt=[9] * 6, max_new=6)
    eng.submit(first)
    eng.run()
    assert first.done
    second = ServeRequest(rid=1, prompt=list(prompt), max_new=5)
    eng.submit(second)                 # re-admits into the dirtied slot
    eng.run()

    fresh = ContinuousBatcher(model, params, max_slots=1, max_seq=64)
    alone = ServeRequest(rid=2, prompt=list(prompt), max_new=5)
    fresh.submit(alone)
    fresh.run()
    assert second.generated == alone.generated


def test_stream_kv_single_device_matches_dense(tmp_path, tiny_model):
    """``stream_kv=True`` without a >1-device mesh degenerates to the
    dense decode path — outputs must be identical token-for-token."""
    model, params = tiny_model
    outs = []
    for stream_kv in (False, True):
        reqs = poisson_trace(4, seed=6)
        eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                          params=params, max_slots=2, max_seq=64,
                          admission="fifo", stream_kv=stream_kv)
        eng.run_trace(reqs)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1]


def test_bounded_queue_rejects_overflow(tmp_path, tiny_model):
    model, params = tiny_model
    tel = Telemetry()
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=1, max_seq=64,
                      max_queue=2, admission="fifo", telemetry=tel)
    reqs = [ServeRequest(rid=i, prompt=[1] * 2, max_new=2)
            for i in range(4)]
    accepted = [eng.submit(r) for r in reqs]
    assert accepted == [True, True, False, False]
    assert [r.rejected for r in reqs] == [False, False, True, True]
    assert tel.counters()["serve.requests_rejected"] == 2
    while eng.step():
        pass
    assert eng.stats()["completed"] == 2 and eng.stats()["rejected"] == 2


# --------------------------------------------------------------------------
# telemetry contract + split-row recording
# --------------------------------------------------------------------------

def test_telemetry_contract(tmp_path, tiny_model):
    """TTFT/per-token histograms, queue-depth gauge, goodput, admission
    instants, and the compiled serve_step's kernel histogram all land in
    the one attached Telemetry — no engine-private counters."""
    model, params = tiny_model
    cache = _synthetic_fitted_cache(tmp_path / "tc")
    tel = Telemetry()
    eng = ServeEngine(model, cache, params=params, max_slots=2,
                      max_seq=96, admission="sjf", telemetry=tel,
                      record_rows=False)
    reqs = [ServeRequest(rid=i, prompt=[1 + i] * (2 + i), max_new=3 + i)
            for i in range(4)]          # all arrive at step 0
    stats = eng.run_trace(reqs)
    assert stats["completed"] == 4
    tokens = stats["tokens_generated"]

    s = tel.summary()["histograms"]
    assert s["serve.ttft_s"]["count"] == 4
    # inter-token gaps: every generated token after a request's first
    assert s["serve.token_latency_s"]["count"] == tokens - 4
    c = tel.counters()
    assert c["serve.requests_completed"] == 4
    assert c["serve.tokens_generated"] == tokens
    # every engine iteration went through the compiled program and its
    # dispatcher (stateful step: never the measuring path)
    assert s["kernel.serve_step.s"]["count"] == stats["engine_steps"]
    assert c["dispatch.predicted"] == stats["engine_steps"]
    assert c.get("dispatch.measured", 0) == 0
    assert "program.wall_s" in s
    # admission instants carry the policy + prediction for each request
    admits = tel.events(cat="admission")
    assert len(admits) == 4
    assert all(e["args"]["policy"] == "sjf"
               and e["args"]["predicted_s"] > 0 for e in admits)
    assert tel.series("serve.queue_depth")
    goodput = tel.series("serve.goodput_tok_s")
    assert goodput and goodput[-1][1] > 0


def test_request_residuals_feed_drift_monitor(tmp_path, tiny_model):
    model, params = tiny_model
    cache = _synthetic_fitted_cache(tmp_path / "tc")
    tel = Telemetry()
    eng = ServeEngine(model, cache, params=params, max_slots=2,
                      max_seq=96, admission="sjf", telemetry=tel,
                      record_rows=False)
    eng.run_trace([ServeRequest(rid=i, prompt=[1] * 4, max_new=4)
                   for i in range(3)])
    drift = tel.to_json()["drift"]["kernels"]
    assert drift["serve.request"]["n"] == 3
    # the drift band is the split model's fit-time MAPE, not a default
    band = split_cost_model_from_cache(cache).fit_band_pct
    assert drift["serve.request"]["fit_band_pct"] == band


def test_completed_requests_record_split_rows(tmp_path, tiny_model):
    model, params = tiny_model
    cache = TuningCache(root=str(tmp_path / "tc"))
    eng = ServeEngine(model, cache, params=params, max_slots=2,
                      max_seq=96, admission="fifo")     # record_rows on
    n = 5
    eng.run_trace([ServeRequest(rid=i, prompt=[1 + i] * 3, max_new=4)
                   for i in range(n)])
    prefill = cache.entry(PREFILL_STEP_KERNEL)
    decode = cache.entry(DECODE_STEP_KERNEL)
    assert prefill.n_rows == n                   # one TTFT row per request
    assert decode.n_rows == n                    # one per-token row each
    assert prefill.feature_names == ["prompt", "ctx"]
    assert decode.feature_names == ["ctx"]
    assert np.all(prefill.y > 0) and np.all(decode.y > 0)
    # enough signal to bootstrap the SJF cost model for the next engine
    m = fit_cost_entries(cache, model_factory=LinearModel, save=False)
    assert m.request_seconds(2, 2) > 0


# --------------------------------------------------------------------------
# bench schema (serve section, schema 4)
# --------------------------------------------------------------------------

def _minimal_serve_section() -> dict:
    pol = {"ttft_s": {"p50": 0.01, "p99": 0.02, "mean": 0.012, "count": 4},
           "token_latency_s": {"p50": 0.002, "p99": 0.003, "mean": 0.002,
                               "count": 12},
           "goodput_tok_s": 500.0, "completed": 4, "rejected": 0,
           "engine_steps": 40, "occupancy": 0.8,
           "admission_fallback": False}
    return {"size": "quick", "model": "yi-9b", "max_slots": 2,
            "max_seq": 96,
            "cost_model": {"prefill_mape_pct": 10.0,
                           "decode_mape_pct": 5.0},
            "traces": {"bursty": {"arrival": "burst", "n_requests": 8,
                                  "policies": {"fifo": pol, "sjf": pol}}},
            "sjf_beats_fifo_bursty": True,
            "telemetry_path": "results/telemetry_serve.json"}


def test_serve_schema_section_validates():
    import copy

    from repro.bench.schema import BENCH_SCHEMA_VERSION, validate_bench

    doc = {"schema": BENCH_SCHEMA_VERSION, "quick": True,
           "generated_unix": 1.0, "host_fingerprint": {},
           "configs": {"cpu": {"kind": "real", "executor": "async",
                               "devices": ["cpu"], "device_mape": {}}},
           "workloads": {"w": {"size": "small", "kernels": ["matmul"],
                               "n_nodes": 1,
                               "configs": {"cpu": {
                                   "n_transfers": 0,
                                   "wall_s": {"best": 1, "default": 1,
                                              "worst": 1},
                                   "predicted_makespan_s": {
                                       "best": 1, "default": 1, "worst": 1},
                                   "speedup_vs_default": 1.0,
                                   "speedup_vs_worst": 1.0,
                                   "overhead": {"dispatch_frac": 0.0,
                                                "executor_frac": 0.0},
                                   "mape": {"matmul": 1.0}}}}},
           "geomean": {"cpu": {"speedup_vs_default": 1.0,
                               "speedup_vs_worst": 1.0}},
           "external": {},
           "serve": _minimal_serve_section()}
    assert validate_bench(doc) is doc
    assert BENCH_SCHEMA_VERSION == 5

    def broken(mutate):
        bad = copy.deepcopy(doc)
        mutate(bad)
        with pytest.raises(ValueError, match="bench.json invalid"):
            validate_bench(bad)

    broken(lambda d: d.__setitem__("schema", 3))     # serve needs >= 4
    broken(lambda d: d["serve"].__delitem__("sjf_beats_fifo_bursty"))
    broken(lambda d: d["serve"]["traces"].__setitem__("bursty", {}))
    broken(lambda d: d["serve"]["traces"]["bursty"]["policies"]["sjf"]
           ["ttft_s"].__delitem__("p99"))
    broken(lambda d: d["serve"]["traces"]["bursty"]["policies"]
           .__setitem__("lifo", d["serve"]["traces"]["bursty"]["policies"]
                        ["fifo"]))
    # schema-3 documents without a serve section stay loadable
    legacy = {k: v for k, v in doc.items() if k != "serve"}
    legacy["schema"] = 3
    assert validate_bench(legacy) is legacy


# --------------------------------------------------------------------------
# decode-time ring KV streaming (4 devices, subprocess)
# --------------------------------------------------------------------------

RING_DECODE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.compat import make_mesh
    from repro.dist.ring_attention import ring_decode
    from repro.models.attention import attend_decode

    mesh = make_mesh((4,), ("model",))
    rng = np.random.RandomState(0)
    b, h, kv, d, smax = 2, 4, 2, 16, 32
    q = jnp.asarray(rng.randn(b, 1, h, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, kv, smax, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, kv, smax, d), jnp.float32)
    # a layer stack whose layer 1 is (k, v): read inside the SPMD body
    k_stack = jnp.stack([v, k, -k])
    v_stack = jnp.stack([k, v, -v])
    for idx in (3, 7, 12, 31):          # shard-interior + boundary indices
        for window in (0, 8):
            for start in (None, jnp.asarray([0, 5], jnp.int32)):
                ref = attend_decode(q, k, v, jnp.int32(idx),
                                    window=window, start=start)
                out = ring_decode(q, k, v, jnp.int32(idx), mesh=mesh,
                                  window=window, start=start)
                err = float(jnp.max(jnp.abs(out - ref)))
                assert err <= 2e-5, (idx, window, start is None, err)
                out = ring_decode(q, k_stack, v_stack, jnp.int32(idx),
                                  mesh=mesh, window=window, start=start,
                                  layer=jnp.int32(1))
                err = float(jnp.max(jnp.abs(out - ref)))
                assert err <= 2e-5, ("stacked", idx, window, err)
    print("RING_DECODE_OK")
""")


@pytest.mark.slow
def test_ring_decode_multidevice_parity():
    r = subprocess.run(
        [sys.executable, "-c", RING_DECODE_SCRIPT],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RING_DECODE_OK" in r.stdout


# --------------------------------------------------------------------------
# the step's spans on the profiler's trace; no telemetry, no calls
# --------------------------------------------------------------------------

def test_step_spans_on_the_profiler_trace(tmp_path, tiny_model):
    """Three engine steps under the profiler: one numbered ``serve.step``
    each, holding admit < assemble < execute < emit; the execute phase
    holds the compiled program's call and the host copy of its tokens, and
    the dispatch runs inside ``exec.task`` on the executor's worker."""
    from _trace import host_spans, profiled

    model, params = tiny_model
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=2, max_seq=64,
                      admission="fifo", record_rows=False)
    eng.submit(ServeRequest(rid=0, prompt=[3, 4], max_new=8))
    eng.step()                               # compiles outside the trace
    with profiled(tmp_path / "trace"):
        for _ in range(3):
            assert eng.step()
    spans = host_spans(tmp_path / "trace", (
        "serve.", "program.", "exec.", "dispatch."))
    steps = [s for s in spans if s.name == "serve.step"]
    assert [s.stats["step_num"] for s in steps] == [1, 2, 3]
    phases = ("serve.admit", "serve.assemble", "serve.execute", "serve.emit")
    for step in steps:
        inside = [s for s in spans if step.holds(s) and s is not step]
        top = [s for s in inside if s.thread == step.thread
               and s.name in phases]
        assert [s.name for s in top] == list(phases)
        assert all(a.end <= b.start for a, b in zip(top, top[1:]))
        execute = top[2]
        names = [s.name for s in inside
                 if execute.holds(s) and s is not execute]
        assert names == ["program.call", "exec.task", "dispatch.serve_step",
                         "dispatch.decide", "dispatch.launch",
                         "dispatch.wait", "serve.fetch"]
        by = {s.name: s for s in inside}
        assert by["program.call"].holds(by["exec.task"])
        assert by["exec.task"].holds(by["dispatch.serve_step"])
        assert by["program.call"].end <= by["serve.fetch"].start
        worker = by["exec.task"].thread
        assert worker != step.thread
        assert all(s.thread == worker for s in inside
                   if s.name.startswith("dispatch."))


def test_engine_without_telemetry_makes_no_telemetry_call(
        tmp_path, tiny_model, monkeypatch):
    from repro.obs.telemetry import NullTelemetry

    calls = []
    for name in ("count", "gauge", "observe", "instant", "event",
                 "residual", "span"):
        monkeypatch.setattr(
            NullTelemetry, name,
            lambda self, *a, _n=name, **k: calls.append(_n))
    model, params = tiny_model
    eng = ServeEngine(model, TuningCache(root=str(tmp_path / "tc")),
                      params=params, max_slots=2, max_seq=64,
                      admission="fifo", record_rows=False)
    assert eng._compiled.telemetry is None   # None stays None below it
    for i in range(3):
        eng.submit(ServeRequest(rid=i, prompt=[1 + i] * 2, max_new=3))
    while eng.step():
        pass
    assert len(eng.completed) == 3
    assert calls == []
