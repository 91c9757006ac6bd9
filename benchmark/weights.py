"""Weights of a llama-style decoder, made on the device from the seed.

Every leaf is drawn from its own key, ``fold_in(seed key, leaf id)``, and
each layer of a stacked leaf from ``fold_in(leaf key, layer)``.  Values
are 17-bit odd integers from the key's random bits times one constant (a
uniform of the stated standard deviation), rounded once to float32 and
once to the served type, so the same key gives the same bfloat16
numbers in whichever program draws them: the serving weights, drawn for
all layers in one jitted call, and the reference's, drawn one layer at a
time after the program's copy is freed.

The tree has the layout of the program's ``Model.param_specs`` for a
``("attn",)`` layer pattern: ``stack/scan/p0`` holds the layers stacked
on a leading axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.counting import Dims

# leaf -> (id, shape without the layer axis, standard deviation); a norm
# scale (standard deviation None) is 1 plus a uniform within 1/8, so a
# scale that is not applied shows
LAYER_LEAVES = {
    ("norm1", "scale"): (0, lambda m: (m.d_model,), None),
    ("attn", "wq"): (1, lambda m: (m.d_model, m.n_heads, m.head_dim),
                     lambda m: m.d_model ** -0.5),
    ("attn", "wk"): (2, lambda m: (m.d_model, m.n_kv_heads, m.head_dim),
                     lambda m: m.d_model ** -0.5),
    ("attn", "wv"): (3, lambda m: (m.d_model, m.n_kv_heads, m.head_dim),
                     lambda m: m.d_model ** -0.5),
    ("attn", "wo"): (4, lambda m: (m.n_heads, m.head_dim, m.d_model),
                     lambda m: (m.n_heads * m.head_dim) ** -0.5),
    ("norm2", "scale"): (5, lambda m: (m.d_model,), None),
    ("mlp", "w_gate"): (6, lambda m: (m.d_model, m.d_ff),
                        lambda m: m.d_model ** -0.5),
    ("mlp", "w_up"): (7, lambda m: (m.d_model, m.d_ff),
                      lambda m: m.d_model ** -0.5),
    ("mlp", "w_down"): (8, lambda m: (m.d_ff, m.d_model),
                        lambda m: m.d_ff ** -0.5),
}
TOP_LEAVES = {
    ("embed", "table"): (100, lambda m: (m.vocab_size, m.d_model),
                         lambda m: 1.0),
    ("final_norm", "scale"): (101, lambda m: (m.d_model,),
                              None),
    ("unembed", "table"): (102, lambda m: (m.vocab_size, m.d_model),
                           lambda m: m.d_model ** -0.5),
}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one wider than 32 bits."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


def uniform(key, shape, std, dtype):
    """A uniform of standard deviation ``std`` (None: a norm scale, 1 plus
    a uniform within 1/8), exactly reproducible from ``key``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    odd = ((bits >> 16).astype(jnp.int32) * 2 - 65535).astype(jnp.float32)
    if std is None:
        # exact: a power-of-two scale and a sum that float32 holds
        return (1.0 + odd * jnp.float32(2.0 ** -19)).astype(dtype)
    return (odd * jnp.float32(std * math.sqrt(3.0) / 65535.0)).astype(dtype)


def _leaf(key, spec, m: Dims, dtype, layer=None):
    lid, shape, std = spec
    k = jax.random.fold_in(key, lid)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return uniform(k, shape(m), None if std is None else std(m), dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for (a, b), v in flat.items():
        out.setdefault(a, {})[b] = v
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer(key, m: Dims, dtype, layer):
    return _nest({path: _leaf(key, spec, m, dtype, layer)
                  for path, spec in LAYER_LEAVES.items()})


def layer(key, m: Dims, layer_index: int, dtype=jnp.bfloat16) -> dict:
    """One layer's weights (the reference's draw)."""
    return _layer(key, m, jnp.dtype(dtype).name, jnp.int32(layer_index))


def top(key, m: Dims, name: str, dtype=jnp.bfloat16) -> jax.Array:
    """One of ``embed``, ``final_norm`` or ``unembed`` (the reference's
    draw)."""
    path = {"embed": ("embed", "table"), "final_norm": ("final_norm", "scale"),
            "unembed": ("unembed", "table")}[name]
    return _top(key, m, jnp.dtype(dtype).name, path)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _top(key, m: Dims, dtype, path):
    return _leaf(key, TOP_LEAVES[path], m, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _params(key, m: Dims, dtype):
    layers = jax.vmap(lambda l: _layer.__wrapped__(key, m, dtype, l))(
        jnp.arange(m.n_layers, dtype=jnp.int32))
    tree = _nest({path: _leaf(key, spec, m, dtype)
                  for path, spec in TOP_LEAVES.items()})
    tree["stack"] = {"scan": {"p0": layers}, "tail": {}}
    return tree


def params(key, m: Dims, dtype=jnp.bfloat16) -> dict:
    """Every weight, in the program's tree, in one jitted call."""
    return _params(key, m, jnp.dtype(dtype).name)
