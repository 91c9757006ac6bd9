"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py`` and on hand-made intervals."""
import os

import pytest

from benchmark import tracereduce as tr

PROBE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    return tr.reduce(PROBE)


def test_device_clock_is_shifted_onto_the_host(probe):
    # the v5e's device nanoseconds read about 1.4 ms early
    assert 1.2e6 < probe.shift_ns < 1.6e6
    steps = [s for s in probe.spans if s.name == "bench.step"]
    assert len(steps) == 6
    for s, e in probe.busy:
        assert any(st.start <= s and e <= st.end for st in steps), (s, e)


def test_busy_is_the_union_and_gaps_go_to_the_host_span(probe):
    lo, hi = probe.window
    assert probe.window_s == pytest.approx(12.063409e-3)
    assert probe.busy_s == pytest.approx(
        sum(e - s for s, e in probe.busy) * 1e-9)
    idle = sum(probe.gaps_s.values())
    assert idle + probe.busy_s == pytest.approx(probe.window_s)
    # three 2 ms sleeps under bench.wait; the rest is host time in steps
    assert probe.gaps_s["bench.wait"] > 6e-3
    assert set(probe.gaps_s) <= {"bench.wait", "bench.step", tr.OTHER}


def test_ops_by_name_are_self_times(probe):
    assert sum(probe.op_s.values()) == pytest.approx(probe.busy_s, rel=0.05)
    names = [n for n, _ in probe.top_ops(10)]
    assert any(n.startswith("convolution_tanh_fusion") for n in names)
    assert all(" = " not in n for n in names)
    assert probe.busy_between(lo := probe.window[0], probe.window[1]) \
        == pytest.approx(probe.busy_s)
    assert probe.busy_between(lo, lo) == 0.0


def test_merge_self_times_and_innermost():
    assert tr.merge([(5, 6), (0, 3), (2, 4), (4, 4)]) == [[0, 4], [5, 6]]
    assert sorted(tr._self_times([(0, 10, "loop"), (1, 3, "a"),
                                  (4, 6, "b")])) == \
        [("a", 2), ("b", 2), ("loop", 6)]
    spans = [tr.Span("bench.call", 0, 10), tr.Span("bench.node.matmul", 2, 5),
             tr.Span("bench.call", 20, 30)]
    starts = [s.start for s in spans]
    assert tr._innermost(spans, starts, 3) == "bench.node.matmul"
    assert tr._innermost(spans, starts, 7) == "bench.call"
    assert tr._innermost(spans, starts, 15) == tr.OTHER
    assert tr._shift([10, 20], [1, 12]) == 9
    assert tr._shift([10], [1, 2]) is None


def test_op_names_are_shortened():
    assert tr.op_name("%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(x)") \
        == "fusion.3 bf16[8,128]"
    assert tr.op_name("plain") == "plain"


def test_predictor_error_is_against_the_host_span():
    """The predictor is fitted to host-clock call times, so its error is
    read against the node's host span, not the device time inside it."""
    import types

    from benchmark import manifest
    from benchmark.record import Run

    red = tr.Reduction(window=(0, 100e6), busy_s=0.0, op_s={}, gaps_s={},
                       busy=[[1e6, 2e6], [40e6, 50e6]], spans=[
                           tr.Span("bench.node.matmul", 0, 10e6),
                           tr.Span("bench.node.flash_attention", 30e6, 50e6)],
                       n_devices=1)
    dec = [types.SimpleNamespace(kernel="matmul", chosen="a",
                                 predicted_s={"a": 0.012}, params={}),
           types.SimpleNamespace(kernel="flash_attention", chosen="b",
                                 predicted_s={"b": 0.015}, params={})]
    run = Run("graph", {}, 1.0, {}, trace=red, extra={"decisions": dec})
    mape = manifest.metric_module(
        os.path.dirname(os.path.dirname(os.path.dirname(PROBE))),
        "predictor_mape_pct")
    # 10 ms span predicted 12 ms, 20 ms span predicted 15 ms: 20% and 25%
    assert mape.read(run) == pytest.approx(22.5)
    run.extra["decisions"] = dec[:1]
    assert mape.read(run) is None
