"""Operation and byte counts, checked against shapes worked by hand."""
import pytest

from benchmark import counting, peaks
from benchmark.record import Run, cache_spans
from benchmark.traffic import Planned

TINY = counting.Dims(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
                     head_dim=2, d_ff=16, vocab_size=10)


def test_layer_and_model_params():
    # q, o: 8*4*2 = 64 each; k, v: 8*2*2 = 32 each; mlp: 3*8*16 = 384
    assert counting.layer_matmul_params(TINY) == 64 + 64 + 32 + 32 + 384
    assert counting.matmul_params(TINY) == 2 * 576 + 10 * 8


def test_yi_9b_stage_weights():
    yi = counting.Dims(n_layers=24, d_model=4096, n_heads=32, n_kv_heads=4,
                       head_dim=128, d_ff=11008, vocab_size=64000)
    # 173 M per layer, 24 layers, plus the 64000 x 4096 head
    assert counting.layer_matmul_params(yi) == 173_015_040
    assert counting.kv_bytes_per_token(yi) == 48 * 1024


def test_serve_step_counts_live_kv_only():
    kv = counting.kv_bytes_per_token(TINY)      # 2 * 2 * 2 * 2 * 2 = 32
    assert kv == 32
    # two slots decoding one token each, at positions 3 and 5
    flops, nbytes = counting.serve_step(TINY, [(2, 3), (4, 5)])
    # two tokens through every weight, plus q.k and p.v over 3 and 5
    # positions: 4 * layers * heads * head_dim * ctx
    assert flops == 2 * 2 * 1232 + 4 * 2 * 4 * 2 * (3 + 5)
    weights = (1232 + 2 * 8 + 5 * 8) * 2
    # 3 and 5 positions read, one written for each
    assert nbytes == weights + (3 + 5 + 2) * kv


def test_serve_step_counts_a_chunk_of_positions():
    # positions 3..5 in one step: three tokens, attending 3, 4 and 5
    flops, nbytes = counting.serve_step(TINY, [(2, 5)])
    assert flops == 3 * 2 * 1232 + 4 * 2 * 4 * 2 * (3 + 4 + 5)
    weights = (1232 + 3 * 8 + 5 * 8) * 2
    assert nbytes == weights + (5 + 3) * 32
    # the same positions one at a time do the same operations
    one = [counting.serve_step(TINY, [(b, b + 1)])[0] for b in (2, 3, 4)]
    assert sum(one) == flops


def _run_with(rides: dict, prompts: dict) -> Run:
    planned = [Planned(rid=r, prompt=[0] * prompts[r], max_new=99,
                       rides=list(v)) for r, v in rides.items()]
    return Run("serve", {}, 1.0, {}, planned=planned)


def test_cache_spans_one_prompt_token_per_step():
    # prompt of 3: three prefill steps, the third gives the first token
    run = _run_with({0: [(0, 0), (1, 0), (2, 1), (3, 2), (4, 3)]}, {0: 3})
    spans = cache_spans(run)
    assert [spans[k] for k in range(5)] == [
        [(0, 1)], [(1, 2)], [(2, 3)], [(3, 4)], [(4, 5)]]


def test_cache_spans_whole_prompt_in_one_step():
    # a prompt of 8 taken whole in the step that gives the first token,
    # beside a request that is still taking one prompt token a step
    run = _run_with({0: [(0, 1), (1, 2)], 1: [(0, 0), (1, 0)]},
                    {0: 8, 1: 5})
    spans = cache_spans(run)
    assert spans[0] == [(0, 8), (0, 1)]
    assert spans[1] == [(8, 9), (1, 2)]
    flops = sum(counting.serve_step(TINY, s)[0] for s in spans.values())
    # 8 + 1 + 2 positions processed in all
    assert flops == 11 * 2 * 1232 + 4 * 2 * 4 * 2 * (36 + 9 + 1 + 2)


def test_matmul_and_causal_attention():
    assert counting.matmul(2, 3, 4) == (48.0, (8 + 12 + 6) * 2.0)
    flops, nbytes = counting.causal_attention(1, 4, 2, 8)
    # 10 visible pairs of 4 positions, 2 heads, 2 products of 2*8 each
    assert flops == 4 * 2 * 8 * 10
    assert nbytes == 4 * 4 * 2 * 8 * 2


def test_roofline_and_unknown_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert peaks.least_seconds(1.0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
