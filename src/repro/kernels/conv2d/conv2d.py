"""Valid 2-D convolution (cross-correlation) Pallas kernel.

Output is tiled on a (m/bm, n/bn) grid; the input stays VMEM-resident and
each tile reads its halo'd window by ``pl.ds`` ref indexing (overlapping
windows are not expressible as strided BlockSpecs).  The r x r taps unroll into
shift-multiply-accumulate over the tile — VPU-friendly, no gathers.  For
inputs beyond VMEM a production schedule would add halo'd double-buffered
DMA; until then ``ops.conv2d`` refuses inputs above
``kernels.RESIDENT_INPUT_MAX_BYTES``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(r, bm, bn, a_ref, w_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    row0 = i * bm
    col0 = j * bn
    tile = a_ref[pl.ds(row0, bm + r - 1),
                 pl.ds(col0, bn + r - 1)].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.zeros((bm, bn), jnp.float32)
    for di in range(r):
        for dj in range(r):
            acc += tile[di:di + bm, dj:dj + bn] * w[di, dj]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def conv2d(a: jax.Array, w: jax.Array, *, bm: int = 128, bn: int = 128,
           interpret: bool) -> jax.Array:
    """a: [m, n], w: [r, r] -> valid correlation [m-r+1, n-r+1] (padded to
    block multiples by ops.py)."""
    m, n = a.shape
    r = w.shape[0]
    om, on = m - r + 1, n - r + 1
    assert om % bm == 0 and on % bn == 0, (om, on, bm, bn)
    kernel = functools.partial(_conv_kernel, r, bm, bn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((om, on), a.dtype),
        grid=(om // bm, on // bn),
        in_specs=[
            pl.BlockSpec(a.shape, lambda i, j: (0, 0)),   # VMEM-resident input
            pl.BlockSpec(w.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(a, w)
