"""Continuous batching: outputs must equal independent greedy generation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.serve.continuous import ContinuousBatcher, Request
from repro.serve.decode import ServeConfig, generate


def _standalone(model, params, prompt, max_new, max_seq):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new, max_seq, ServeConfig())
    return [int(t) for t in np.asarray(out[0])]


@pytest.mark.slow
def test_matches_independent_generation():
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in rng.randint(1, cfg.vocab_size, n)],
                    max_new=5)
            for i, n in enumerate([4, 7, 3, 5, 6])]

    engine = ContinuousBatcher(model, params, max_slots=2, max_seq=64)
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert all(r.done for r in reqs)
    assert stats["occupancy"] > 0.5          # slots actually stay busy

    for r in reqs:
        expected = _standalone(model, params, r.prompt, r.max_new, 64)
        assert r.generated == expected, (r.rid, r.generated, expected)


def test_cost_aware_admission_orders_queue():
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    cost = lambda plen, mnew: plen + mnew    # NN+C stand-in
    engine = ContinuousBatcher(model, params, max_slots=1, max_seq=64,
                               cost_model=cost)
    long_req = Request(0, [1] * 10, max_new=3)
    short_req = Request(1, [1] * 2, max_new=3)
    engine.submit(long_req)
    engine.submit(short_req)
    engine.step()
    # shortest-predicted-job-first: the short request takes the single slot
    assert engine.slots[0] is short_req


# (arch, n_layers): scanned and tail layers, local windows, experts and
# recurrent state that a new tenant's slot must not inherit
STAGGERED = {
    "local+tail": ("gemma3-1b", 8),
    "moe+tail": ("llama4-maverick-400b-a17b", 3),
    "hybrid": ("hymba-1.5b", 2),
}


def _engine_tokens(model, params, reqs, max_slots):
    """Each request's tokens from one engine, and the cache index at which
    each was admitted."""
    engine = ContinuousBatcher(model, params, max_slots=max_slots,
                               max_seq=64)
    starts = []
    engine._on_admit = lambda req, slot: starts.append(
        int(engine.start[slot]))
    own = [Request(r.rid, list(r.prompt), r.max_new) for r in reqs]
    for r in own:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in own)
    return [r.generated for r in own], starts


@pytest.mark.parametrize("stack", sorted(STAGGERED))
def test_staggered_slots_match_independent_generation(stack):
    """Slots admitted at different steps (their own ``start``) write into
    and read from the shared stacked cache, and each request generates
    what the same engine generates for it alone (one slot, start 0).  The
    reference takes the engine's own path, token by token through the
    bfloat16 cache, so near-ties between logits fall alike on both sides."""
    arch, n_layers = STAGGERED[stack]
    cfg = dataclasses.replace(ARCHS[arch].reduced(), n_layers=n_layers,
                              compute_dtype="float32", capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in rng.randint(1, cfg.vocab_size, n)],
                    max_new=m)
            for i, (n, m) in enumerate([(5, 4), (9, 3), (3, 6), (6, 4)])]
    batched, starts = _engine_tokens(model, params, reqs, max_slots=2)
    assert len(set(starts)) >= 3, starts      # admitted at staggered steps
    for r, got in zip(reqs, batched):
        alone, _ = _engine_tokens(model, params, [r], max_slots=1)
        assert got == alone[0], (stack, r.rid, got, alone[0])


# -- the whole-prompt path: one prefill per admitted request ----------------

@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _prompt(rng, n, vocab=256):
    return [int(t) for t in rng.randint(1, vocab, n)]


def _admissions(engine):
    """(rid, slot, index, start) of every admission, recorded as it
    happens."""
    seen = []
    engine._on_admit = lambda req, slot: seen.append(
        (req.rid, slot, engine.index, int(engine.start[slot])))
    return seen


def test_whole_prompt_matches_independent_generation(dense):
    """Prompts on both sides of a bucket edge (31, 32, 33) and one that
    fills the row behind the index (P == I), each prefilled in one call,
    generate what independent greedy generation does."""
    model, params = dense
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=_prompt(rng, n), max_new=5)
            for i, n in enumerate([31, 32, 33, 64])]
    engine = ContinuousBatcher(model, params, max_slots=2, max_seq=128)
    assert engine.whole_prompt
    assert engine.buckets == (32, 64, 128)
    seen = _admissions(engine)
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert all(r.done for r in reqs)
    assert stats["prefills"] == 4
    assert stats["prefill_tokens"] == 31 + 32 + 33 + 64
    # every prompt sits right behind the index it was admitted at
    assert all(start == index - len(reqs[rid].prompt)
               for rid, _, index, start in seen)
    assert (3, 1, 64, 0) in seen                         # P == I
    # a request decodes prompt + answer - 1 positions, not one per step
    assert stats["engine_steps"] == 2 * (5 - 1)
    for r in reqs:
        expected = _standalone(model, params, r.prompt, r.max_new, 128)
        assert r.generated == expected, (r.rid, r.generated, expected)


def test_whole_prompt_answers_of_one_token_free_their_slot(dense):
    """A request whose prefill gives its only token is done at admission;
    its slot takes the next request at once, so more such requests than
    slots all complete without a decode step."""
    model, params = dense
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i, prompt=_prompt(rng, 5 + i), max_new=1)
            for i in range(5)]
    engine = ContinuousBatcher(model, params, max_slots=2, max_seq=64)
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert all(r.done for r in reqs) and not engine.queue
    assert stats["prefills"] == 5 and stats["engine_steps"] == 0
    for r in reqs:
        assert r.generated == _standalone(model, params, r.prompt, 1, 64)


def test_whole_prompt_slot_reused_over_stale_rows(dense):
    """A slot's second tenant is prefilled over rows its first tenant
    wrote (part of them below the new start, part inside the new prompt,
    part ahead of the index) and still generates what it would alone."""
    model, params = dense
    rng = np.random.RandomState(2)
    first = Request(0, _prompt(rng, 40), max_new=20)
    second = Request(1, _prompt(rng, 9), max_new=6)
    engine = ContinuousBatcher(model, params, max_slots=1, max_seq=128)
    seen = _admissions(engine)
    engine.submit(first)
    engine.run()
    engine.submit(second)
    engine.run()
    (_, s0, i0, st0), (_, s1, i1, st1) = seen
    assert s0 == s1 == 0
    # the second tenant's rows [st1, i1 + 5) lie inside the first's
    # [st0, i0 + 19): stale K/V below, inside and ahead of the new prompt
    assert st0 < st1 and i1 + 5 < i0 + 19
    for r in (first, second):
        assert r.generated == _standalone(model, params, r.prompt,
                                          r.max_new, 128), r.rid


def test_whole_prompt_padded_block_edge(dense):
    """The padded prompt block may end exactly at ``max_seq`` (admitted);
    one row later it would be clamped, so that request waits for a drain
    and is admitted after it, lower down."""
    model, params = dense
    rng = np.random.RandomState(4)
    engine = ContinuousBatcher(model, params, max_slots=3, max_seq=64)
    seen = _admissions(engine)
    long_ = Request(0, _prompt(rng, 4), max_new=30)
    engine.submit(long_)
    engine.step()
    assert seen == [(0, 0, 32, 28)]                 # rebased to max_seq // 2
    engine.step()
    # index 34: 34 - 2 + 32 == 64, the edge
    edge = Request(1, _prompt(rng, 2), max_new=3)
    engine.submit(edge)
    engine.step()
    assert seen[-1] == (1, 1, 34, 32)
    # index 35: 35 - 2 + 32 == 65, one past it; a free slot is held
    past = Request(2, _prompt(rng, 2), max_new=3)
    engine.submit(past)
    while not long_.done:
        assert engine.queue[0] is past and past.generated == []
        engine.step()
    engine.run()
    assert past.done and seen[-1] == (2, 0, 32, 30)
    for r in (long_, edge, past):
        assert r.generated == _standalone(model, params, r.prompt,
                                          r.max_new, 64), r.rid


@pytest.mark.parametrize("prompt_len,max_new,index", [
    (20, 4, 64),        # max_seq // 2
    (100, 4, 100),      # the head's prompt, past the middle
    (10, 100, 28),      # its answer needs the row ahead: no lower than P
])
def test_index_after_full_drain(dense, prompt_len, max_new, index):
    """With every slot drained the shared index moves to
    max(max_seq // 2, P_head), unless the head's answer needs more room
    ahead of it."""
    model, params = dense
    rng = np.random.RandomState(5)
    engine = ContinuousBatcher(model, params, max_slots=2, max_seq=128)
    seen = _admissions(engine)
    engine.submit(Request(0, _prompt(rng, 3), max_new=2))
    engine.run()
    assert engine.index > 64 and all(s is None for s in engine.slots)
    engine.submit(Request(1, _prompt(rng, prompt_len), max_new=max_new))
    engine.step()
    assert seen[-1] == (1, 0, index, index - prompt_len)


def _one_token_alone(model, params, prompt, max_new, max_seq, stream_kv):
    """A request alone through the engine's decode step, its prompt and
    then its own tokens fed one per step from index 0."""
    from repro.serve.continuous import _jitted_step

    step = _jitted_step(model, stream_kv)
    cache = model.init_cache(1, max_seq)
    start = jnp.zeros((1,), jnp.int32)
    out = []
    for i in range(len(prompt) + max_new - 1):
        tok = prompt[i] if i < len(prompt) else out[-1]
        nxt, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32),
                          jnp.int32(i), start)
        if i >= len(prompt) - 1:
            out.append(int(np.asarray(nxt)[0, 0]))
    return out


# (arch, n_layers, stream_kv): each keeps the one-token path
ONE_TOKEN = {
    "local": ("gemma3-1b", 8, False),
    "moe": ("llama4-maverick-400b-a17b", 3, False),
    "mlstm+slstm": ("xlstm-1.3b", 2, False),
    "hybrid": ("hymba-1.5b", 2, False),
    "stream_kv": ("yi-9b", 2, True),
}


@pytest.mark.parametrize("stack", sorted(ONE_TOKEN))
def test_one_token_path_kept(stack):
    """Ring buffers, expert capacity, recurrent state and a streamed cache
    keep one prompt token per step: no prefill, and the tokens of one
    token at a time through the decode step."""
    arch, n_layers, stream_kv = ONE_TOKEN[stack]
    cfg = dataclasses.replace(ARCHS[arch].reduced(), n_layers=n_layers,
                              compute_dtype="float32", capacity_factor=8.0)
    if stack == "mlstm+slstm":
        cfg = dataclasses.replace(cfg, layer_pattern=("mlstm", "slstm"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(6))
    rng = np.random.RandomState(6)
    reqs = [Request(rid=i, prompt=_prompt(rng, n, cfg.vocab_size),
                    max_new=m)
            for i, (n, m) in enumerate([(5, 4), (9, 3), (3, 6)])]
    engine = ContinuousBatcher(model, params, max_slots=2, max_seq=64,
                               stream_kv=stream_kv)
    assert not engine.whole_prompt
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert stats["prefills"] == 0 and stats["prefill_tokens"] == 0
    for r in reqs:
        assert r.generated == _one_token_alone(
            model, params, r.prompt, r.max_new, 64, stream_kv), r.rid
