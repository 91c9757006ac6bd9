"""Matrix-vector Pallas kernel: row-blocked, column-scanned.

MV is bandwidth-bound: each A tile is read once, the x tile is reused
across the row grid, and the per-row fp32 partials accumulate in VMEM.
Every block is 2-D, as the chip's tiled layout needs: x rides as a
``[1, k]`` row broadcast over the A tile, and the result leaves as an
``[m, 1]`` column (a lane reduction on the VPU — no MXU pass with N=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mv_kernel(a_ref, x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    a = a_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)              # [1, bk]
    acc_ref[...] += jnp.sum(a * x, axis=1, keepdims=True)
    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def matvec(a: jax.Array, x: jax.Array, *, bm: int = 256, bk: int = 512,
           interpret: bool) -> jax.Array:
    """a: [m, k], x: [1, k] -> [m, 1]; m % bm == 0 and k % bk == 0
    (ops.py pads and reshapes)."""
    m, k = a.shape
    assert x.shape == (1, k), (a.shape, x.shape)
    assert m % bm == 0 and k % bk == 0
    return pl.pallas_call(
        _mv_kernel,
        out_shape=jax.ShapeDtypeStruct((m, 1), a.dtype),
        grid=(m // bm, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, l: (i, l)),
            pl.BlockSpec((1, bk), lambda i, l: (0, l)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, l: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(a, x)
