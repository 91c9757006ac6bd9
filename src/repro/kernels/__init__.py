"""Shared kernel-dispatch policy helpers + the abstract-value contract."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np


class Aval(NamedTuple):
    """Shape/dtype abstract value for the ``abstract_params``/``out_aval``
    hooks every ``ops.py`` entry point exposes.  The hooks only ever read
    ``.shape`` and ``.dtype``, so concrete jax/numpy arrays, lazy traced
    values, and these Avals are all interchangeable inputs."""
    shape: tuple
    dtype: object


def default_interpret(backend: Optional[str] = None) -> bool:
    """Whether Pallas kernels run in interpret mode: only on the CPU, which
    has no Pallas lowering.  Every accelerator runs the compiled kernel."""
    return (backend or jax.default_backend()) == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> backend-derived default.  An explicit ``True`` on a TPU
    is refused: an interpreted kernel there would hide the device."""
    if interpret is None:
        return default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("interpret=True on a TPU: Pallas kernels run "
                         "compiled on the chip")
    return bool(interpret)


# The stencil kernels (blur, conv2d, maxpool) hold their whole input in one
# VMEM block.  On a TPU v5e the compiler accepts such a block up to about
# 16 MiB of tiled footprint (rows padded to the sublane tile, columns to 128
# lanes); this bound keeps a margin for the output tiles.  Inputs within it
# compile for the chip (tests/test_tpu_compile.py); larger ones need a
# halo'd, tiled DMA schedule the kernels do not have yet.
RESIDENT_INPUT_MAX_BYTES = 15 * 2 ** 20


def vmem_footprint(shape, dtype) -> int:
    """Bytes a block of ``shape`` occupies in VMEM under the (8, 128)
    32-bit tiling (narrower dtypes pack more rows per sublane tile)."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    *lead, rows, cols = shape
    return int(np.prod(lead, dtype=np.int64)) * (-(-rows // sub) * sub) \
        * (-(-cols // 128) * 128) * itemsize


def check_resident_input(kernel: str, shape, dtype) -> None:
    """Raise for a stencil input whose VMEM-resident block cannot fit."""
    nbytes = vmem_footprint(shape, dtype)
    if nbytes > RESIDENT_INPUT_MAX_BYTES:
        raise ValueError(
            f"{kernel}: input block {tuple(shape)} {np.dtype(dtype).name} "
            f"occupies {nbytes} bytes of VMEM; the Pallas kernel keeps its "
            f"whole padded input there and takes at most "
            f"{RESIDENT_INPUT_MAX_BYTES} — use the reference path "
            "(use_kernel=False) for larger inputs")
