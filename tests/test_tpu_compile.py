"""Compile the main path's Pallas kernels for a described TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip.  They catch what
interpret mode cannot: blocks that do not tile (8, 128), layouts Mosaic
refuses, and VMEM overflows.  Nothing runs, so nothing here says anything
about results or times.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.kernels.blur import ops as blur_ops
from repro.kernels.conv2d import ops as conv2d_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.matmul import ops as matmul_ops
from repro.kernels.maxpool import ops as maxpool_ops

PALLAS_KERNELS = ("matmul", "matvec", "conv2d", "maxpool")


# the described chip: ``topo`` and ``one_chip`` (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("no_compile_cache")


@pytest.fixture(scope="module")
def chip_registry():
    """The registry, traced as it is on the chip: the backend here is the
    CPU, so the kernels' interpret default is steered to the chip's."""
    from repro.runtime import default_registry
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.kernels, "default_interpret",
                   lambda backend=None: False)
        yield default_registry()


@pytest.fixture(scope="module")
def large_nodes():
    """(kernel, params, operand avals) of every distinct node of the five
    workloads at their ``large`` preset."""
    from repro.workloads import get_workload, workload_names
    out, seen = [], set()
    for name in workload_names():
        prog = get_workload(name).build(size="large").program
        avals = {s.name: s.aval for s in prog.inputs}
        for node in prog.nodes:
            avals[node.name] = node.aval
            key = (node.kernel, tuple(sorted(node.params.items())))
            if key not in seen:
                seen.add(key)
                out.append((node.kernel, dict(node.params),
                            [avals[d] for d in node.deps]))
    return out


def _compile(fn, one_chip, *avals):
    """Lower + compile ``fn`` for the described chip; returns the HLO."""
    args = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype, sharding=one_chip)
            for a in avals]
    return jax.jit(fn).lower(*args).compile().as_text()


def _aval(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_every_pallas_kernel_is_compiled_here(chip_registry):
    """A kernel the registry gains with a Pallas variant must join the
    parametrization below."""
    with_pallas = {k for k in chip_registry.kernels()
                   if any(v.name.startswith("pallas")
                          for v in chip_registry.variants(k))}
    assert with_pallas == set(PALLAS_KERNELS)


@pytest.mark.parametrize("kernel", PALLAS_KERNELS)
def test_registry_pallas_variants_compile_at_large(kernel, chip_registry,
                                                   large_nodes, one_chip):
    nodes = [n for n in large_nodes if n[0] == kernel]
    variants = [v for v in chip_registry.variants(kernel)
                if v.name.startswith("pallas")]
    assert nodes and variants
    for _, params, avals in nodes:
        for v in variants:
            hlo = _compile(lambda *args, v=v, p=params: v.call(args, p),
                           one_chip, *avals)
            assert "tpu_custom_call" in hlo, (kernel, v.name, params)


def test_matmul_at_yi_9b_width(one_chip):
    """The MLP up-projection of yi-9b: [256, 4096] @ [4096, 11008], bf16."""
    for blk in (128, 256):
        fn = functools.partial(matmul_ops.matmul, bm=blk, bn=blk, bk=blk,
                               interpret=False)
        hlo = _compile(fn, one_chip, _aval((256, 4096), jnp.bfloat16),
                       _aval((4096, 11008), jnp.bfloat16))
        assert "tpu_custom_call" in hlo


ATTN = dict(b=1, h=32, kv=4, s=1024, d=128)    # yi-9b's heads, GQA 32:4


def _attention_avals():
    b, h, kv, s, d = (ATTN[k] for k in ("b", "h", "kv", "s", "d"))
    return (_aval((b, h, s, d), jnp.bfloat16),
            _aval((b, kv, s, d), jnp.bfloat16),
            _aval((b, kv, s, d), jnp.bfloat16))


def _attention(q, k, v):
    return fa_ops.attention(q, k, v, causal=True, interpret=False)


def test_flash_attention_forward_compiles(one_chip):
    assert "tpu_custom_call" in _compile(_attention, one_chip,
                                         *_attention_avals())


def test_flash_attention_backward_compiles(one_chip):
    def loss(q, k, v):
        return jnp.sum(_attention(q, k, v).astype(jnp.float32))

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                   *_attention_avals())
    # forward (with its log-sum-exp), dq sweep and dk/dv sweep
    assert hlo.count("tpu_custom_call") >= 3


# the largest inputs the VMEM guard admits, and the next size up
STENCILS = {
    "blur": (lambda a: blur_ops.blur(a, interpret=False), (1794, 1794),
             (1922, 1922)),
    "blur_separable": (lambda a: blur_ops.blur(a, separable=True,
                                               interpret=False),
                       (1794, 1794), (1922, 1922)),
    "conv2d": (lambda a: conv2d_ops.conv2d(a, jnp.ones((3, 3), a.dtype),
                                           interpret=False),
               (1794, 1794), (1922, 1922)),
    "maxpool": (lambda a: maxpool_ops.maxpool(a, r=2, s=2, interpret=False),
                (1792, 1792), (2048, 2048)),
}


@pytest.mark.parametrize("name", sorted(STENCILS))
def test_stencil_compiles_at_the_guard_limit(name, one_chip):
    fn, fits, _ = STENCILS[name]
    assert "tpu_custom_call" in _compile(fn, one_chip, _aval(fits))


@pytest.mark.parametrize("name", sorted(STENCILS))
def test_stencil_guard_refuses_above_the_limit(name, one_chip):
    fn, _, too_big = STENCILS[name]
    with pytest.raises(ValueError, match="VMEM"):
        _compile(fn, one_chip, _aval(too_big))
