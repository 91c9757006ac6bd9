"""Public maxpool op: output-grid padding + stride-phase split."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import Aval, check_resident_input, resolve_interpret
from repro.kernels.maxpool import maxpool as _kernel
from repro.kernels.maxpool import ref as _ref


def abstract_params(a, *, r: int, s: int) -> dict:
    """Predictor params from avals (shape-only; see kernels/matmul/ops.py).
    ``r``/``s`` are static keyword operands and ride along as params."""
    m, n = a.shape
    return {"m": int(m), "n": int(n), "r": int(r), "s": int(s)}


def out_aval(a, *, r: int, s: int) -> Aval:
    m, n = a.shape
    return Aval(((m - r) // s + 1, (n - r) // s + 1), a.dtype)


def _stride_phases(a: jax.Array, *, r: int, s: int, bm: int,
                   bn: int) -> jax.Array:
    """[s*s, om' + h, on' + h] stride planes of ``a`` (h = (r-1)//s, om'/on'
    the output extents rounded up to the block), padded with the dtype's
    lowest value so padded windows never win the max."""
    m, n = a.shape
    om, on = (m - r) // s + 1, (n - r) // s + 1
    halo = (r - 1) // s
    mp = -(-om // bm) * bm + halo
    np_ = -(-on // bn) * bn + halo
    low = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) \
        else jnp.iinfo(a.dtype).min
    a = a[:s * mp, :s * np_]
    a = jnp.pad(a, ((0, s * mp - a.shape[0]), (0, s * np_ - a.shape[1])),
                constant_values=low)
    return a.reshape(mp, s, np_, s).transpose(1, 3, 0, 2).reshape(
        s * s, mp, np_)


def maxpool(a: jax.Array, *, r: int, s: int, bm: int = 128, bn: int = 128,
            use_kernel: bool = True,
            interpret: Optional[bool] = None) -> jax.Array:
    if not use_kernel:
        return _ref.maxpool(a, r=r, s=s)
    interpret = resolve_interpret(interpret)
    m, n = a.shape
    om, on = (m - r) // s + 1, (n - r) // s + 1
    phases = _stride_phases(a, r=r, s=s, bm=bm, bn=bn)
    check_resident_input("maxpool", phases.shape, phases.dtype)
    out = _kernel.maxpool(phases, r=r, s=s, bm=bm, bn=bn,
                          interpret=interpret)
    return out[:om, :on]
