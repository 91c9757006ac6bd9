"""Blocked matmul Pallas kernel (MXU-aligned, fp32 VMEM accumulator).

Grid (m/bm, n/bn, k/bk); the k axis is innermost so the accumulator tile
stays resident in VMEM across the contraction.  Block sizes are the
*schedule* — the variant axis the NN+C selector tunes (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool) -> jax.Array:
    """a: [m, k] @ b: [k, n]; dims must be multiples of the block shape
    (ops.py pads).  interpret=True validates on CPU; False targets TPU,
    where bm must be a multiple of 8 and bn, bk multiples of 128."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _mm_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
