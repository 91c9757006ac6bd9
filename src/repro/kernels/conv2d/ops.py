"""Public conv2d op: pads the *output* grid to block multiples."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import Aval, check_resident_input, resolve_interpret
from repro.kernels.conv2d import conv2d as _kernel
from repro.kernels.conv2d import ref as _ref


def abstract_params(a, w) -> dict:
    """Predictor params from avals (shape-only; see kernels/matmul/ops.py)."""
    m, n = a.shape
    return {"m": int(m), "n": int(n), "r": int(w.shape[0])}


def out_aval(a, w) -> Aval:
    r = w.shape[0]
    return Aval((a.shape[0] - r + 1, a.shape[1] - r + 1), a.dtype)


def conv2d(a: jax.Array, w: jax.Array, *, bm: int = 128, bn: int = 128,
           use_kernel: bool = True,
           interpret: Optional[bool] = None) -> jax.Array:
    if not use_kernel:
        return _ref.conv2d(a, w)
    interpret = resolve_interpret(interpret)
    m, n = a.shape
    r = w.shape[0]
    om, on = m - r + 1, n - r + 1
    pm, pn = (-om) % bm, (-on) % bn
    ap = jnp.pad(a, ((0, pm), (0, pn))) if (pm or pn) else a
    check_resident_input("conv2d", ap.shape, ap.dtype)
    out = _kernel.conv2d(ap, w, bm=bm, bn=bn, interpret=interpret)
    return out[:om, :on]
