"""Public matvec op with padding + dispatch."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import Aval, resolve_interpret
from repro.kernels.matvec import matvec as _kernel
from repro.kernels.matvec import ref as _ref


def abstract_params(a, x) -> dict:
    """Predictor params from avals (shape-only; see kernels/matmul/ops.py)."""
    m, k = a.shape
    if x.shape and int(x.shape[0]) != int(k):
        raise ValueError(f"matvec contraction dims disagree: "
                         f"a is {tuple(a.shape)}, x is {tuple(x.shape)}")
    return {"m": int(m), "k": int(k)}


def out_aval(a, x) -> Aval:
    return Aval((a.shape[0],), a.dtype)


def matvec(a: jax.Array, x: jax.Array, *, bm: int = 256, bk: int = 512,
           use_kernel: bool = True,
           interpret: Optional[bool] = None) -> jax.Array:
    if not use_kernel:
        return _ref.matvec(a, x)
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    pm, pk = (-m) % bm, (-k) % bk
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    xp = jnp.pad(x, (0, pk)) if pk else x
    out = _kernel.matvec(ap, xp.astype(ap.dtype)[None, :], bm=bm, bk=bk,
                         interpret=interpret)
    return out[:m, 0]
