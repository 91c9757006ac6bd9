"""Step-by-step decode must reproduce the parallel forward pass — this
validates the chunkwise mLSTM/SSM math and the KV-cache plumbing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model

CASES = ["yi-9b", "gemma3-1b", "hymba-1.5b", "xlstm-1.3b", "nemotron-4-15b"]


@pytest.mark.parametrize("arch_name", CASES)
def test_decode_matches_forward(arch_name):
    cfg = dataclasses.replace(ARCHS[arch_name].reduced(),
                              compute_dtype="float32", capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.random.RandomState(0).randint(
        1, cfg.vocab_size, (B, S)), jnp.int32)
    logits_fwd, _ = model.forward(params, {"tokens": toks, "labels": toks},
                                  remat=False)
    cache = model.init_cache(B, S, cache_dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
        outs.append(lg)
    err = float(jnp.max(jnp.abs(logits_fwd - jnp.concatenate(outs, 1))))
    scale = float(jnp.max(jnp.abs(logits_fwd))) + 1e-9
    assert err / scale < 1e-4, (err, scale)


def test_prefill_then_decode_matches_forward():
    """prefill fills the cache correctly: decode continues seamlessly."""
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.random.RandomState(1).randint(
        1, cfg.vocab_size, (B, S)), jnp.int32)
    logits_fwd, _ = model.forward(params, {"tokens": toks, "labels": toks},
                                  remat=False)
    pre = S - 4
    logits_pre, cache = model.prefill(params, {"tokens": toks[:, :pre]},
                                      max_seq=S, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_fwd[:, :pre]),
                               rtol=2e-3, atol=2e-3)
    outs = []
    for t in range(pre, S):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(logits_fwd[:, pre:]),
                               rtol=2e-3, atol=2e-3)


# stacks with scanned periods and unscanned tail layers: (arch, n_layers)
STACKS = {
    "local+tail": ("gemma3-1b", 8),          # 5 local + attn, then 2 local
    "moe+tail": ("llama4-maverick-400b-a17b", 3),     # attn, moe, then attn
    "hybrid": ("hymba-1.5b", 3),
    "recurrent+tail": ("xlstm-1.3b", 10),    # 7 mlstm + slstm, then 2 mlstm
    "cross": ("whisper-medium", 3),          # read-only encoder K/V
}


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_prefill_then_decode_matches_forward_stacks(stack):
    """Prefill writes the cache in its decode layout for scanned and tail
    layers alike; decode then writes each token into its layer in place."""
    arch, n_layers = STACKS[stack]
    cfg = dataclasses.replace(ARCHS[arch].reduced(), n_layers=n_layers,
                              compute_dtype="float32", capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.random.RandomState(2).randint(
        1, cfg.vocab_size, (B, S)), jnp.int32)
    extra = {}
    if cfg.frontend == "frame":
        extra["frames"] = jnp.asarray(np.random.RandomState(3).randn(
            B, cfg.n_frontend_tokens, cfg.d_model) * 0.05, jnp.float32)
    logits_fwd, _ = model.forward(
        params, {"tokens": toks, "labels": toks, **extra}, remat=False)
    pre = S - 5
    logits_pre, cache = model.prefill(
        params, {"tokens": toks[:, :pre], **extra}, max_seq=S,
        cache_dtype=jnp.float32)
    assert jax.tree.structure(cache) == jax.tree.structure(
        model.init_cache(B, S, cache_dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_fwd[:, :pre]),
                               rtol=2e-3, atol=2e-3)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    outs = []
    for t in range(pre, S):
        lg, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(logits_fwd[:, pre:]),
                               rtol=2e-3, atol=2e-3)
