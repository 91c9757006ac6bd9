"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

with the peaks read from ``PEAKS`` by the chip's ``device_kind``.

``compiled.cost_analysis()`` is *per-device* post-SPMD (verified empirically:
a 2x16x32x64 einsum over 8 devices reports ~65536/8 flops), so global =
per-device * chips and the task formulas reduce to per-device / per-chip-*.
Collective bytes are parsed from the post-SPMD HLO text: the summed result
bytes of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute ops (start/done variants counted once).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")


@dataclasses.dataclass(frozen=True)
class Peak:
    """Published per-chip peak rates of one device kind."""
    flops_bf16: float        # FLOP/s
    hbm_bytes_per_s: float
    ici_bytes_per_s: float   # per link
    source: str


# keyed by jax's ``device.device_kind``
PEAKS = {
    "TPU v5 lite": Peak(
        flops_bf16=197e12, hbm_bytes_per_s=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bytes_per_s=50e9,
        source="Google Cloud documentation, TPU v5e"),
}


def peak_for(device_kind: str) -> Peak:
    """The peak table entry of ``device_kind``; an unknown kind is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, e.g. 'bf16[8,128]{1,0}' or a tuple."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-kind summed result bytes of collective ops in post-SPMD HLO."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        lhs, rhs = line.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(\([^)]*\)|\S+)\s+([\w-]+)", rhs)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        for kind in _COLLECTIVE_KINDS:
            # count the -start variant once; skip -done (same payload)
            if op == kind or op == f"{kind}-start":
                out[kind] += shape_bytes(shape_str)
                break
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # trip-count-corrected analytic terms (primary; see hlo_analysis.py)
    per_device_flops: float
    per_device_bytes: float
    per_device_collective_bytes: float
    collective_breakdown: dict
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    bottleneck: str
    # raw cost_analysis (loop bodies counted once — reference only)
    raw_flops: float = 0.0
    raw_bytes: float = 0.0
    memory_per_device_bytes: Optional[dict] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(arch: str, shape: str, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, model_flops: float,
            memory_stats: Optional[dict] = None, *,
            device_kind: str) -> RooflineReport:
    from repro.launch import hlo_analysis
    peak = peak_for(device_kind)
    totals = hlo_analysis.analyze_hlo(hlo_text)
    flops = totals.flops
    bytes_accessed = totals.hbm_bytes
    coll = {k: float(v) for k, v in totals.collective_bytes.items()}
    coll_total = float(sum(coll.values()))
    compute_s = flops / peak.flops_bf16
    memory_s = bytes_accessed / peak.hbm_bytes_per_s
    collective_s = coll_total / peak.ici_bytes_per_s
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    hlo_global = flops * chips
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        per_device_flops=flops, per_device_bytes=bytes_accessed,
        per_device_collective_bytes=coll_total, collective_breakdown=coll,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model_flops, hlo_flops_global=hlo_global,
        useful_ratio=(model_flops / hlo_global) if hlo_global else 0.0,
        bottleneck=bottleneck,
        raw_flops=float(cost.get("flops", 0.0)),
        raw_bytes=float(cost.get("bytes accessed", 0.0)),
        memory_per_device_bytes=memory_stats)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (the analytic "c = f(K,H)" of this framework — see DESIGN.md)
# ---------------------------------------------------------------------------

def count_params_split(model) -> tuple[int, int]:
    """(total_params, active_params): MoE experts count top_k/E when active."""
    import jax
    from repro.models.module import ParamSpec

    cfg = model.cfg
    specs = model.param_specs()
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))[0]
    total = active = 0
    for path, leaf in flat:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += n
        keys = [getattr(k, "key", str(k)) for k in path]
        is_expert = "moe" in keys and any(
            k in ("w_gate", "w_up", "w_down") for k in keys) and "shared" not in keys
        if is_expert:
            active += n * cfg.moe_top_k // max(cfg.n_experts, 1)
        else:
            active += n
    return total, active


def model_flops(model, shape) -> float:
    """6*N_active*D for train; 2*N_active*D forward-only (prefill);
    2*N_active*B per decode step."""
    _, active = count_params_split(model)
    if shape.is_decode:
        return 2.0 * active * shape.global_batch
    factor = 2.0 if shape.kind == "prefill" else 6.0
    return factor * active * shape.global_batch * shape.seq_len
