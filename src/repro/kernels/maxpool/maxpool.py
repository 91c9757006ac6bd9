"""Max-pooling Pallas kernel (window r, stride s), output-tiled.

Same halo'd-window pattern as conv2d, with the stride taken out of the
kernel: ``ops.py`` splits the input into its s*s stride phases (plane
``p*s + q`` holds ``a[p::s, q::s]``), so tap (di, dj) of output (i, j) is
element (i + di//s, j + dj//s) of plane (di%s, dj%s).  Every tap is then a
unit-stride window of one plane — the chip's vector slices allow no
stride above 1 — and the running max stays in registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mp_kernel(r, s, bm, bn, a_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    halo = (r - 1) // s
    acc = jnp.full((bm, bn), -jnp.inf, jnp.float32)
    for p in range(min(r, s)):
        for q in range(min(r, s)):
            tile = a_ref[p * s + q, pl.ds(i * bm, bm + halo),
                         pl.ds(j * bn, bn + halo)].astype(jnp.float32)
            for oi in range((r - 1 - p) // s + 1):
                for oj in range((r - 1 - q) // s + 1):
                    acc = jnp.maximum(acc, tile[oi:oi + bm, oj:oj + bn])
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "s", "bm", "bn", "interpret"))
def maxpool(phases: jax.Array, *, r: int, s: int, bm: int = 128,
            bn: int = 128, interpret: bool) -> jax.Array:
    """phases: [s*s, om + (r-1)//s, on + (r-1)//s] stride planes (ops.py
    builds them) with om % bm == 0 and on % bn == 0 -> [om, on]."""
    planes, mp, np_ = phases.shape
    assert planes == s * s, (phases.shape, s)
    halo = (r - 1) // s
    om, on = mp - halo, np_ - halo
    assert om % bm == 0 and on % bn == 0, (om, on, bm, bn)
    kernel = functools.partial(_mp_kernel, r, s, bm, bn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((om, on), phases.dtype),
        grid=(om // bm, on // bn),
        in_specs=[pl.BlockSpec(phases.shape, lambda i, j: (0, 0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(phases)
