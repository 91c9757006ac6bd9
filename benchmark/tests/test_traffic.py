"""The traffic generator, and how the metrics count late and unfinished
requests."""
import json
import os

import numpy as np
import pytest

from benchmark import manifest, traffic
from benchmark.record import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHAT = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                   "chat.json")))
BIG = 2 ** 33 + 12345


def test_same_seed_same_requests():
    a = traffic.requests(CHAT, BIG, 64000, 50)
    b = traffic.requests(CHAT, BIG, 64000, 50)
    assert [(p.prompt, p.max_new, p.due) for p in a] == \
        [(p.prompt, p.max_new, p.due) for p in b]
    c = traffic.requests(CHAT, BIG + 1, 64000, 50)
    assert [p.prompt for p in a] != [p.prompt for p in c]


def test_every_seed_same_sizes_and_times_other_tokens():
    a = traffic.requests(CHAT, 1, 64000, 200)
    b = traffic.requests(CHAT, 2, 64000, 200)
    assert [(len(p.prompt), p.max_new, p.due) for p in a] == \
        [(len(p.prompt), p.max_new, p.due) for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]
    other = dict(CHAT, schedule_seed=CHAT["schedule_seed"] + 1)
    c = traffic.requests(other, 1, 64000, 200)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in c)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]


def test_lognormal_clipped_and_centred():
    rng = np.random.default_rng(0)
    spec = CHAT["prompt"]
    n = traffic.lengths(spec, 1001, rng)
    assert n.min() >= spec["min"] and n.max() <= spec["max"]
    assert np.median(n) == spec["median"]


def test_open_loop_rate():
    reqs = traffic.requests(CHAT, 5, 64000, 4000)
    rate = len(reqs) / reqs[-1].due
    assert rate == pytest.approx(CHAT["rate_per_s"], rel=0.01)


def test_closed_loop_has_no_due_times():
    reason = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                         "reason.json")))
    reqs = traffic.requests(reason, 5, 1000, 20)
    assert all(p.due is None for p in reqs)
    assert all(32 <= len(p.prompt) <= 128 for p in reqs)


def _run(planned, window=(10.0, 20.0)):
    run = Run("serve", {}, 10.0, {}, window=window, planned=planned)
    run.extra["t0"] = 0.0
    return run


def _metric(name):
    return manifest.metric_module(ROOT, name)


def test_lateness_counts_from_due_time():
    p = traffic.Planned(0, [1], 1, due=3.0, sent=3.5)
    q = traffic.Planned(1, [1], 1, due=4.0, sent=4.0)
    late = traffic.lateness([p, q], 0.0)
    assert late["max"] == pytest.approx(0.5)
    assert late["n"] == 2


def test_ttft_counts_from_due_and_keeps_unfinished():
    # due in the window [10, 20): served 2 s after due; sent late (which
    # counts); no first token by the close (waits until 20); refused (the
    # same); due before the window (left out)
    a = traffic.Planned(0, [1], 4, due=11.0, sent=11.0, token_times=[13.0])
    b = traffic.Planned(1, [1], 4, due=12.0, sent=14.0, token_times=[15.0])
    c = traffic.Planned(2, [1], 4, due=15.0, sent=15.0)
    d = traffic.Planned(3, [1], 4, due=16.0, sent=16.0, rejected=True)
    early = traffic.Planned(4, [1], 4, due=5.0, sent=5.0, token_times=[40.0])
    waits = sorted([2.0, 3.0, 5.0, 4.0])
    got = _metric("ttft_p90_s").read(_run([a, b, c, d, early]))
    assert got == pytest.approx(np.percentile(waits, 90))


def test_itl_and_output_rate_count_the_window_only():
    a = traffic.Planned(0, [1], 4, due=1.0,
                        token_times=[9.0, 9.5, 10.5, 11.0, 21.0])
    run = _run([a])
    # gaps ending in [10, 20): 1.0 and 0.5 s
    assert _metric("itl_p95_ms").read(run) == pytest.approx(
        np.percentile([1000.0, 500.0], 95))
    assert _metric("output_tok_s").read(run) == pytest.approx(2 / 10.0)


def test_queue_wait_censors_at_close():
    class Req:
        admitted_s = None
    a = traffic.Planned(0, [1], 4, due=12.0, req=Req())
    assert _metric("queue_wait_p90_s").read(_run([a])) == pytest.approx(8.0)
