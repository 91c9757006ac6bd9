"""The decode step writes each layer's new K/V token into the stacked cache
in place and reads the layer where it lies.

Two checks per stack of each cache-bearing kind (attn, local, moe, hybrid),
a stack with tail layers and a cross-attention model:

* in the jaxpr of ``Model.decode_step`` every ``dynamic_update_slice`` of a
  K/V cache leaf writes one position (sequence extent 1), so no layer is
  copied out and written back, and the cross-attention pair is never
  written;
* compiled for a TPU v5e (described, not attached) with the cache donated,
  the step needs less scratch memory than one layer's K and V.  The widths
  are the chat deployment's cache (24 slots x 3072 positions x 4 KV heads x
  128) under a small model: a layer's K+V (151 MB) exceeds the chip's VMEM,
  so a copied layer has to live in HBM, where this check sees it.  The CPU
  backend cannot show the property: its dots read their operands from
  buffers of their own, and it widens bfloat16 operands to float32.  Heads
  of 128 and of 64 both: the chip stores a cache of 64-wide heads
  sequence-minor, and the step has to write it in that layout.

On four described chips the serving rules split the cache over its heads,
or with ``long_context`` over its sequence, and the step compiled under
either (and through the decode ring) keeps each chip's shard where it lies:
no gather of the cache, no copy of a layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS
from repro.dist import compat, sharding as shd
from repro.models import attention, build_model
from repro.models.module import is_spec, shape_tree

# compiles for the described chip: ``topo``, ``one_chip`` (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("no_compile_cache")

B, S = 24, 3072
WIDTHS = dict(n_heads=8, n_kv_heads=4, head_dim=128, param_dtype="bfloat16")

# (arch, n_layers): each scans at least two periods, so the layer loop stays
# a loop when compiled
CASES = {
    "attn": ("yi-9b", 4),
    "local": ("gemma3-1b", 12),
    "moe": ("qwen3-moe-235b-a22b", 4),
    "hybrid": ("hymba-1.5b", 4),
    "tail": ("llama4-maverick-400b-a17b", 5),     # (attn, moe) x 2 + attn
    "cross": ("whisper-medium", 4),
}


def _model(case, **widths):
    arch, n_layers = CASES[case]
    cfg = dataclasses.replace(ARCHS[arch].reduced(), n_layers=n_layers,
                              **{**WIDTHS, **widths})
    return cfg, build_model(cfg)


def _kv_shapes(cache_specs):
    """Shapes of the written (k, v) and read-only (xk, xv) cache leaves."""
    written, read_only = [], []

    def walk(tree):
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                walk(leaf)
            elif name in ("k", "v"):
                written.append(tuple(leaf.shape))
            elif name in ("xk", "xv"):
                read_only.append(tuple(leaf.shape))
    walk(cache_specs)
    return written, read_only


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            for sub in subs:
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def _step_args(model, sharding=None, params=None, cache=None, max_seq=S):
    """Shapes of ``decode_step``'s arguments; ``params`` and ``cache`` are
    sharding trees (``sharding`` for each leaf without them)."""
    def put(tree, shardings):
        if shardings is None:
            shardings = jax.tree.map(lambda _: sharding, tree)
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)
    ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=sharding)
    return (put(shape_tree(model.param_specs()), params),
            put(shape_tree(model.cache_specs(B, max_seq)), cache),
            ints((B, 1)), ints(()), ints((B,)))


def _step(model, stream_kv=False):
    def step(params, cache, tokens, index, start):
        return model.decode_step(params, cache, tokens, index, start=start,
                                 stream_kv=stream_kv)
    return step


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_writes_one_token_per_kv_leaf(case):
    cfg, model = _model(case)
    written, read_only = _kv_shapes(model.cache_specs(B, S))
    assert written and (case != "cross" or read_only)
    assert not set(written) & set(read_only)
    jaxpr = jax.make_jaxpr(_step(model))(*_step_args(model)).jaxpr
    writes = 0
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name != "dynamic_update_slice":
            continue
        operand = tuple(eqn.invars[0].aval.shape)
        update = tuple(eqn.invars[1].aval.shape)
        assert operand not in read_only, (
            f"{case}: the cross-attention cache {operand} is written")
        if operand in written:
            # [.., B, KV, S, D]: one position, every slot and head
            assert update[-2] == 1, (
                f"{case}: {update} written into the K/V leaf {operand}")
            assert update[-4:-2] == operand[-4:-2] and update[-1] == operand[-1]
            writes += 1
    # one write per K and per V leaf: each scanned kind once in the loop
    # body, each tail layer once
    assert writes == len(written), (case, writes, len(written))


@pytest.fixture
def chip_layouts(topo, monkeypatch):
    """Traces here run on the CPU; the layouts the step pins its cache to
    are asked of the described chip instead."""
    monkeypatch.setattr(attention, "layout_device", lambda: topo.devices[0])


def _assert_copies_no_layer(case, one_chip, **widths):
    cfg, model = _model(case, **widths)
    layer_kv = 2 * B * cfg.n_kv_heads * S * cfg.resolved_head_dim * 2
    compiled = jax.jit(_step(model), donate_argnums=(1,)).lower(
        *_step_args(model, one_chip)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_kv, (case, widths, temp, layer_kv)


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_decode_copies_no_layer(case, one_chip, chip_layouts):
    _assert_copies_no_layer(case, one_chip)


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_decode_copies_no_layer_at_head_dim_64(case, one_chip,
                                                        chip_layouts):
    _assert_copies_no_layer(case, one_chip, n_heads=16, head_dim=64)


# -- four chips -------------------------------------------------------------

@pytest.fixture(scope="module")
def four_chips(topo):
    return compat.make_mesh((1, 4), ("data", "model"),
                            devices=topo.devices[:4])


def _kv_leaf_shardings(model, mesh, rules, max_seq):
    """(logical axes, PartitionSpec) of each ``k``/``v`` cache leaf."""
    specs = model.cache_specs(B, max_seq)
    shardings = shd.tree_shardings(specs, mesh, rules)
    paths = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)[0]
    out = [(spec.logical_axes, sh.spec) for (path, spec), sh
           in zip(paths, jax.tree.leaves(shardings))
           if path[-1].key in ("k", "v")]
    assert out
    return out


@pytest.mark.parametrize("long_context", [False, True],
                         ids=["heads", "long_context"])
def test_serve_rules_split_the_cache(long_context, four_chips):
    """``serve_rules()`` splits the K/V cache over its heads and
    ``long_context`` over its sequence, whichever the leaf names first;
    4 KV heads would also divide over the four chips."""
    cfg, model = _model("attn")
    assert cfg.n_kv_heads % 4 == 0
    split = "cache_seq" if long_context else "kv_heads"
    rules = shd.serve_rules(long_context=long_context)
    for axes, spec in _kv_leaf_shardings(model, four_chips, rules, S):
        assert [axes[i] for i, p in enumerate(spec) if p == "model"] == [
            split], (axes, spec)


@pytest.mark.parametrize("long_context,stream_kv",
                         [(False, False), (True, False), (True, True)],
                         ids=["heads", "long_context", "long_context-ring"])
def test_sharded_decode_copies_no_layer(long_context, stream_kv, four_chips):
    """Each chip holds the chat deployment's cache geometry (24 x 3072
    positions x 4 heads of one layer, 151 MB); the step compiled for the
    four needs less scratch than one layer's K and V on a chip, so it
    neither gathers the cache nor copies a layer of its shard."""
    cfg, model = _model("attn")
    rules = shd.serve_rules(long_context=long_context)
    max_seq = 4 * S
    rep = NamedSharding(four_chips, P())
    params = shd.tree_shardings(model.param_specs(), four_chips, rules)
    cache = shd.tree_shardings(model.cache_specs(B, max_seq), four_chips,
                               rules)

    def step(*args):
        with shd.use_mesh(four_chips, rules):
            return _step(model, stream_kv)(*args)

    compiled = jax.jit(step, donate_argnums=(1,),
                       out_shardings=(rep, cache)).lower(
        *_step_args(model, rep, params, cache, max_seq)).compile()
    layer_kv = 2 * B * cfg.n_kv_heads * max_seq * cfg.resolved_head_dim * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_kv // 4, (temp, layer_kv // 4)
