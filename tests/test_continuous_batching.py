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
