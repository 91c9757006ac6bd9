"""Public blur op + the measurable host-side schedule variants for §6.

``blur`` pads and dispatches the Pallas kernel (TPU target, interpret
validated).  ``HOST_SCHEDULES`` / ``host_blur_time`` provide genuinely
measurable schedule variants on the container CPU (jnp implementations with
real runtime differences) for the Fig-4 variant-selection benchmark.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import Aval, check_resident_input, resolve_interpret
from repro.kernels.blur import blur as _kernel
from repro.kernels.blur import ref as _ref


def abstract_params(a) -> dict:
    """Predictor params from avals (shape-only; see kernels/matmul/ops.py)."""
    m, n = a.shape
    return {"m": int(m), "n": int(n)}


def out_aval(a) -> Aval:
    return Aval((a.shape[0] - 2, a.shape[1] - 2), a.dtype)


def blur(a: jax.Array, *, bm: int = 128, bn: int = 128,
         separable: bool = False, use_kernel: bool = True,
         interpret: Optional[bool] = None) -> jax.Array:
    if not use_kernel:
        return _ref.blur(a)
    interpret = resolve_interpret(interpret)
    m, n = a.shape
    om, on = m - 2, n - 2
    pm, pn = (-om) % bm, (-on) % bn
    ap = jnp.pad(a, ((0, pm), (0, pn))) if (pm or pn) else a
    # the separable schedule's first pass reads rows padded to a bm multiple
    rows = ap.shape[0] + ((-ap.shape[0]) % bm if separable else 0)
    check_resident_input("blur", (rows, ap.shape[1]), ap.dtype)
    out = _kernel.blur(ap, bm=bm, bn=bn, separable=separable,
                       interpret=interpret)
    return out[:om, :on]


# --- measurable host variants (Fig 4) ---------------------------------------

def _host_direct(a):
    return _ref.blur(a)


def _host_separable(a):
    m, n = a.shape
    h = (a[:, 0:n - 2] + a[:, 1:n - 1] + a[:, 2:n]).astype(jnp.float32) / 3.0
    v = (h[0:m - 2] + h[1:m - 1] + h[2:m]) / 3.0
    return v.astype(a.dtype)


def _host_conv(a):
    k = jnp.ones((3, 3), a.dtype) / 9.0
    return jax.lax.conv_general_dilated(
        a[None, None], k[None, None], (1, 1), "VALID")[0, 0]


def _host_blocked(a, tile):
    m, n = a.shape
    om, on = m - 2, n - 2
    nb = max(1, om // tile)
    rows = []
    for i in range(nb):
        r0 = i * (om // nb)
        r1 = om if i == nb - 1 else (i + 1) * (om // nb)
        rows.append(_ref.blur(a[r0:r1 + 2]))
    return jnp.concatenate(rows, axis=0)


HOST_SCHEDULES = {
    "direct": lambda a: _host_direct(a),
    "separable": lambda a: _host_separable(a),
    "conv": lambda a: _host_conv(a),
    "blocked64": lambda a: _host_blocked(a, 64),
    "blocked256": lambda a: _host_blocked(a, 256),
}

# schedule feature encoding for the NN+C selector: (sep, conv, n_blocks)
SCHEDULE_FEATURES = {
    "direct": (0.0, 0.0, 1.0),
    "separable": (1.0, 0.0, 1.0),
    "conv": (0.0, 1.0, 1.0),
    "blocked64": (0.0, 0.0, 64.0),
    "blocked256": (0.0, 0.0, 256.0),
}


def host_blur_time(schedule: str, m: int, n: int,
                   rng: np.random.RandomState, reps: int = 3) -> float:
    a = jnp.asarray(rng.rand(m, n), jnp.float32)
    fn = jax.jit(HOST_SCHEDULES[schedule])
    fn(a).block_until_ready()              # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best
