"""3x3 box blur Pallas kernel with a Halide-style schedule space (§6).

Schedule knobs (the *variant* axis the NN+C selector searches):
  * bm, bn       — output tile shape (VMEM working set / locality)
  * separable    — fused 3x3 pass vs two 1-D passes (compute/traffic trade)

Changing the schedule never changes the output — only the runtime — which
is exactly the property the paper exploits for variant selection.  Callers
use ops.blur, which handles all padding; the kernels here require exact
block multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _blur_direct_kernel(bm, bn, a_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = a_ref[pl.ds(i * bm, bm + 2),
                 pl.ds(j * bn, bn + 2)].astype(jnp.float32)
    acc = jnp.zeros((bm, bn), jnp.float32)
    for di in range(3):
        for dj in range(3):
            acc += tile[di:di + bm, dj:dj + bn]
    o_ref[...] = (acc * (1.0 / 9.0)).astype(o_ref.dtype)


def _blur_h_kernel(bm, bn, a_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = a_ref[pl.ds(i * bm, bm),
                 pl.ds(j * bn, bn + 2)].astype(jnp.float32)
    acc = tile[:, 0:bn] + tile[:, 1:bn + 1] + tile[:, 2:bn + 2]
    o_ref[...] = (acc * (1.0 / 3.0)).astype(o_ref.dtype)


def _blur_v_kernel(bm, bn, a_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = a_ref[pl.ds(i * bm, bm + 2),
                 pl.ds(j * bn, bn)].astype(jnp.float32)
    acc = tile[0:bm] + tile[1:bm + 1] + tile[2:bm + 2]
    o_ref[...] = (acc * (1.0 / 3.0)).astype(o_ref.dtype)


def _pallas_2d(kernel, in_arr, out_shape, grid, bm, bn, interpret):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, in_arr.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(in_arr.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(in_arr)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "separable", "interpret"))
def blur(a: jax.Array, *, bm: int = 128, bn: int = 128,
         separable: bool = False, interpret: bool) -> jax.Array:
    """a: [om+2, on+2] with om % bm == 0 and on % bn == 0 -> [om, on]."""
    m, n = a.shape
    om, on = m - 2, n - 2
    assert om % bm == 0 and on % bn == 0, (om, on, bm, bn)

    if not separable:
        return _pallas_2d(functools.partial(_blur_direct_kernel, bm, bn),
                          a, (om, on), (om // bm, on // bn), bm, bn, interpret)

    # pass 1 (horizontal) over om+2 rows, padded up to a bm multiple
    rows1 = om + 2
    pad1 = (-rows1) % bm
    a1 = jnp.pad(a, ((0, pad1), (0, 0))) if pad1 else a
    h = _pallas_2d(functools.partial(_blur_h_kernel, bm, bn),
                   a1, (rows1 + pad1, on),
                   ((rows1 + pad1) // bm, on // bn), bm, bn, interpret)
    # pass 2 (vertical) consumes om+2 rows of h
    h2 = h[:om + 2]
    return _pallas_2d(functools.partial(_blur_v_kernel, bm, bn),
                      h2, (om, on), (om // bm, on // bn), bm, bn, interpret)
