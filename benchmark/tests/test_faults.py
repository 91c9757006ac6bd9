"""A run with the timed path broken underneath must come out not correct.

Each test skips only the look for a chip: it drives ``run.execute`` on a
tiny cell, with one fault planted in the program the window drives.
"""
import time

import jax.numpy as jnp
import pytest

from benchmark import peaks
from benchmark import run as bench_run

from tiny import BIG_SEED, cell

PEAK = peaks.PEAKS["TPU v5 lite"]


def _execute(c, seconds=2.0):
    return bench_run.execute(c, BIG_SEED, seconds, False, PEAK,
                             time.perf_counter())


def test_a_sound_run_is_correct():
    out = _execute(cell("serve", "open"))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def _broken_step(monkeypatch, fault):
    from repro.serve import continuous
    real = continuous._jitted_step

    def jitted_step(model, stream_kv):
        step = real(model, stream_kv)

        def broken(params, cache, tokens, index, start):
            if fault == "state_unchanged":
                kept = continuous.jax.tree.map(jnp.copy, cache)
                tok, cache = step(params, cache, tokens, index, start)
                return tok, kept
            tok, cache = step(params, cache, tokens, index, start)
            if fault == "half_batch":
                half = tok.shape[0] // 2
                tok = tok.at[half:].set(0)
            elif fault == "token_altered":
                tok = (tok + 1) % model.cfg.vocab_size
            return tok, cache

        return broken

    continuous._STEP_FNS.clear()
    monkeypatch.setattr(continuous, "_jitted_step", jitted_step)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    out = _execute(cell("serve"))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_graph_fault_is_not_correct(monkeypatch, fault):
    from repro.kernels.matmul import ops
    real = ops.matmul

    def broken(a, b, **kw):
        out = real(a, b, **kw)
        if fault == "half_batch":
            return out.at[out.shape[0] // 2:].set(0)
        return out.at[0, 0].add(1.0)

    monkeypatch.setattr(ops, "matmul", broken)
    out = _execute(cell("graph"))
    assert not out["correct"], out["checks"]


def test_graph_sound_run_is_correct():
    out = _execute(cell("graph"))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"program_ms", "setup_s"}
