"""Unified variant registry for predictor-driven dispatch.

One interface over every variant axis the repo already has:

- the Pallas kernels' block schedules and their jnp reference paths
  (``repro/kernels/*/ops.py``),
- the blur host schedules of the Fig-4 demonstration,
- the chunked-attention (q_chunk, k_chunk) schedule axis of
  ``repro/autotune/tuner.py``.

A ``Variant`` is (name, call, features, flops): ``features(params)`` is the
NN+C input row *without* c — the variant axis (block size, schedule) is
encoded as trailing feature columns so one per-kernel model ranks all
variants — and ``flops(params)`` is the analytic operation count, the
paper's ``c`` augmentation, appended as the last column by
``KernelRegistry.feature_rows``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.core.features import blur_complexity
from repro.kernels import Aval
from repro.kernels.blur.ops import HOST_SCHEDULES, SCHEDULE_FEATURES
from repro.models.attention import attend_chunked, attend_full


def attention_flops(b: int, h: int, s: int, d: int) -> float:
    """Analytic c for one causal attention call (qk^T + pv)."""
    return 4.0 * b * h * s * s * d


# Single source of truth for the chunked-attention (q_chunk, k_chunk)
# schedule axis.  ATTENTION_SCHEDULE_GRID is the full measurement sweep the
# autotuner walks (repro/autotune/tuner.py imports it); ATTENTION_SCHEDULES
# is the curated subset the dispatcher ranks at run time.
ATTENTION_SCHEDULE_GRID = tuple((q, k) for q in (64, 128, 256, 512)
                                for k in (128, 256, 512, 1024))
ATTENTION_SCHEDULES = ((128, 256), (256, 512), (512, 1024))


@dataclasses.dataclass(frozen=True)
class Variant:
    kernel: str
    name: str
    call: Callable          # call(args: tuple, params: dict) -> jax value
    features: Callable      # features(params) -> list[float]  (no c)
    flops: Callable         # flops(params) -> float  (the c augmentation)


@dataclasses.dataclass(frozen=True)
class RegisteredKernel:
    name: str
    params_of: Callable     # params_of(*args, **kwargs) -> dict
    feature_names: tuple    # column names, c excluded (it is always last)
    variants: tuple
    # the uniform abstract hooks: shape-only derivations so the repro.api
    # tracer can build predictor features and output avals without executing
    abstract_params: Optional[Callable] = None  # (*avals, **kw) -> params
    out_aval: Optional[Callable] = None         # (*avals, **kw) -> Aval


class KernelRegistry:
    def __init__(self):
        self._kernels: dict[str, RegisteredKernel] = {}

    def register(self, rk: RegisteredKernel) -> None:
        if rk.name in self._kernels:
            raise ValueError(f"kernel {rk.name!r} already registered")
        if not rk.variants:
            raise ValueError(f"kernel {rk.name!r} has no variants")
        self._kernels[rk.name] = rk

    def get(self, kernel: str) -> RegisteredKernel:
        if kernel not in self._kernels:
            raise KeyError(f"unknown kernel {kernel!r}; registered: "
                           f"{sorted(self._kernels)}")
        return self._kernels[kernel]

    def kernels(self) -> list[str]:
        return sorted(self._kernels)

    def variants(self, kernel: str) -> tuple:
        return self.get(kernel).variants

    def variant_names(self, kernel: str) -> list[str]:
        return [v.name for v in self.get(kernel).variants]

    def params_of(self, kernel: str, *args, **kwargs) -> dict:
        return self.get(kernel).params_of(*args, **kwargs)

    def abstract_params(self, kernel: str, *avals, **kwargs) -> dict:
        """Predictor params from abstract values (anything with .shape)."""
        rk = self.get(kernel)
        if rk.abstract_params is None:
            raise NotImplementedError(
                f"kernel {kernel!r} registered without an abstract_params "
                "hook; it cannot be traced")
        return rk.abstract_params(*avals, **kwargs)

    def out_aval(self, kernel: str, *avals, **kwargs) -> Aval:
        """Output shape/dtype from abstract values, without executing."""
        rk = self.get(kernel)
        if rk.out_aval is None:
            raise NotImplementedError(
                f"kernel {kernel!r} registered without an out_aval hook; "
                "it cannot be traced")
        return rk.out_aval(*avals, **kwargs)

    def feature_rows(self, kernel: str, params: dict) -> np.ndarray:
        """[n_variants, F+1] candidate matrix, c as the LAST column (the
        layout ``nnc.slice_features`` and the whole perfdata pipeline use)."""
        rk = self.get(kernel)
        rows = [list(v.features(params)) + [v.flops(params)]
                for v in rk.variants]
        return np.asarray(rows, dtype=np.float64)


# --------------------------------------------------------------------------
# Default registry: the repo's own kernels
# --------------------------------------------------------------------------

def _matmul() -> RegisteredKernel:
    from repro.kernels.matmul import ops

    flops = lambda p: 2.0 * p["m"] * p["n"] * p["k"]

    def feat(block, pallas):
        return lambda p: [p["m"], p["n"], p["k"], block, pallas]

    ref = jax.jit(lambda a, b: ops.matmul(a, b, use_kernel=False))
    variants = [Variant("matmul", "ref",
                        lambda args, p: ref(*args), feat(0.0, 0.0), flops)]
    # Pallas variants are named pallas_<block edge>; on the TPU a block must
    # tile (8, 128), so no edge is below 128
    for blk in (128, 256):
        call = jax.jit(lambda a, b, _blk=blk: ops.matmul(
            a, b, bm=_blk, bn=_blk, bk=_blk))
        variants.append(Variant(
            "matmul", f"pallas_{blk}",
            lambda args, p, _c=call: _c(*args), feat(float(blk), 1.0), flops))
    return RegisteredKernel("matmul", ops.abstract_params,
                            ("m", "n", "k", "block", "pallas"),
                            tuple(variants),
                            abstract_params=ops.abstract_params,
                            out_aval=ops.out_aval)


def _matvec() -> RegisteredKernel:
    from repro.kernels.matvec import ops

    flops = lambda p: 2.0 * p["m"] * p["k"]

    def feat(block, pallas):
        return lambda p: [p["m"], p["k"], block, pallas]

    ref = jax.jit(lambda a, x: ops.matvec(a, x, use_kernel=False))
    pall = jax.jit(lambda a, x: ops.matvec(a, x, bm=128, bk=128))
    return RegisteredKernel(
        "matvec", ops.abstract_params, ("m", "k", "block", "pallas"),
        (Variant("matvec", "ref", lambda args, p: ref(*args),
                 feat(0.0, 0.0), flops),
         Variant("matvec", "pallas_128", lambda args, p: pall(*args),
                 feat(128.0, 1.0), flops)),
        abstract_params=ops.abstract_params, out_aval=ops.out_aval)


def _conv2d() -> RegisteredKernel:
    from repro.kernels.conv2d import ops

    flops = lambda p: 2.0 * (p["m"] - p["r"] + 1) * (p["n"] - p["r"] + 1) \
        * p["r"] ** 2

    def feat(block, pallas):
        return lambda p: [p["m"], p["n"], p["r"], block, pallas]

    ref = jax.jit(lambda a, w: ops.conv2d(a, w, use_kernel=False))
    pall = jax.jit(lambda a, w: ops.conv2d(a, w, bm=128, bn=128))
    return RegisteredKernel(
        "conv2d", ops.abstract_params, ("m", "n", "r", "block", "pallas"),
        (Variant("conv2d", "ref", lambda args, p: ref(*args),
                 feat(0.0, 0.0), flops),
         Variant("conv2d", "pallas_128", lambda args, p: pall(*args),
                 feat(128.0, 1.0), flops)),
        abstract_params=ops.abstract_params, out_aval=ops.out_aval)


def _maxpool() -> RegisteredKernel:
    from repro.kernels.maxpool import ops, ref as ref_mod

    flops = lambda p: float((p["m"] // p["s"]) * (p["n"] // p["s"])
                            * p["r"] ** 2)

    def feat(block, pallas):
        return lambda p: [p["m"], p["n"], p["r"], p["s"], block, pallas]

    ref = jax.jit(ref_mod.maxpool, static_argnames=("r", "s"))
    pall = jax.jit(lambda a, r, s: ops.maxpool(a, r=r, s=s, bm=128, bn=128),
                   static_argnames=("r", "s"))
    return RegisteredKernel(
        "maxpool", ops.abstract_params, ("m", "n", "r", "s", "block", "pallas"),
        (Variant("maxpool", "ref",
                 lambda args, p: ref(args[0], r=p["r"], s=p["s"]),
                 feat(0.0, 0.0), flops),
         Variant("maxpool", "pallas_128",
                 lambda args, p: pall(args[0], r=p["r"], s=p["s"]),
                 feat(128.0, 1.0), flops)),
        abstract_params=ops.abstract_params, out_aval=ops.out_aval)


def _blur() -> RegisteredKernel:
    from repro.kernels.blur import ops

    flops = lambda p: blur_complexity(p)

    variants = []
    for sched, fn in HOST_SCHEDULES.items():
        sep, conv, nblk = SCHEDULE_FEATURES[sched]
        call = jax.jit(fn)
        variants.append(Variant(
            "blur", sched, lambda args, p, _c=call: _c(args[0]),
            lambda p, _f=(sep, conv, nblk): [p["m"], p["n"], *_f], flops))
    return RegisteredKernel("blur", ops.abstract_params,
                            ("m", "n", "separable", "conv", "n_blocks"),
                            tuple(variants),
                            abstract_params=ops.abstract_params,
                            out_aval=ops.out_aval)


def _flash_attention() -> RegisteredKernel:
    # this variant set is built over models.attention ([B, S, H, D] layout),
    # so its abstract hooks live here, not in kernels/flash_attention/ops.py
    # (whose entry point is [B, H, S, D])
    def abstract_params(q, k, v):
        b, s, h, d = q.shape
        return {"b": int(b), "h": int(h), "s": int(s), "d": int(d)}

    def out_aval(q, k, v):
        return Aval(tuple(q.shape), q.dtype)

    flops = lambda p: attention_flops(p["b"], p["h"], p["s"], p["d"])

    def feat(qc, kc):
        # qc/kc == 0 encodes "no tiling" (the full reference path)
        return lambda p: [p["b"], p["h"], p["s"], p["d"],
                          qc or p["s"], kc or p["s"]]

    full = jax.jit(lambda q, k, v: attend_full(q, k, v, causal=True))
    variants = [Variant("flash_attention", "full",
                        lambda args, p: full(*args), feat(0, 0), flops)]
    for qc, kc in ATTENTION_SCHEDULES:
        call = jax.jit(lambda q, k, v, _qc=qc, _kc=kc: attend_chunked(
            q, k, v, causal=True, q_chunk=_qc, k_chunk=_kc))
        variants.append(Variant(
            "flash_attention", f"chunked_q{qc}_k{kc}",
            lambda args, p, _c=call: _c(*args), feat(qc, kc), flops))
    return RegisteredKernel("flash_attention", abstract_params,
                            ("b", "h", "s", "d", "q_chunk", "k_chunk"),
                            tuple(variants),
                            abstract_params=abstract_params,
                            out_aval=out_aval)


_BUILDERS = {
    "matmul": _matmul,
    "matvec": _matvec,
    "conv2d": _conv2d,
    "maxpool": _maxpool,
    "blur": _blur,
    "flash_attention": _flash_attention,
}


def default_registry(include: Sequence[str] = ()) -> KernelRegistry:
    """Registry over the repo's kernels; ``include`` restricts the set
    (each registered kernel jit-wraps its variants, so tests/benchmarks
    that touch one kernel should build only that one)."""
    reg = KernelRegistry()
    for name, build in _BUILDERS.items():
        if include and name not in include:
            continue
        reg.register(build())
    return reg
