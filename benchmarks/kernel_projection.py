"""Pallas-kernel projection for the memory term (§Perf iteration).

The jnp flash-attention path materialises its tiles at HLO boundaries; on
TPU the Pallas kernel (repro/kernels/flash_attention) keeps them in VMEM
and HBM sees only q/k/v/out (+ the backward's reads and dq/dk/dv).  This
script MEASURES the HLO-modeled per-device attention traffic by lowering an
isolated per-device-shaped attention fwd+bwd and running the same
trip-count-aware analyzer, then substitutes the kernel-boundary bytes:

  adjusted_mem = mem - n_calls * (T_hlo_attn - T_kernel_attn) / HBM bandwidth

Reported per hillclimb cell as the 'pallas' projection (EXPERIMENTS.md
§Perf).  The kernel itself is validated vs its oracle in tests/.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis
from repro.launch.roofline import peak_for
from repro.models.attention import attend_chunked


def attention_hlo_traffic(b, h, s, d, *, k_chunk=1024, q_chunk=512,
                          window=0) -> tuple[float, float]:
    """(fwd bytes, fwd+bwd bytes) of the jnp flash path, per device."""
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def fwd(q, k, v):
        return attend_chunked(q, k, v, causal=True, window=window,
                              k_chunk=k_chunk, q_chunk=q_chunk)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    t_f = hlo_analysis.analyze_hlo(
        jax.jit(fwd).lower(q, q, q).compile().as_text()).hbm_bytes
    t_fb = hlo_analysis.analyze_hlo(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
        .compile().as_text()).hbm_bytes
    return t_f, t_fb


def kernel_boundary_traffic(b, h, s, d, kv_heads=None) -> tuple[float, float]:
    """(fwd, fwd+bwd) bytes the Pallas kernel moves through HBM."""
    kv = kv_heads or h
    qb = b * s * h * d * 2
    kvb = 2 * b * s * kv * d * 2
    ob = qb
    fwd = qb + kvb + ob
    # bwd: read q,k,v,o,do + write dq,dk,dv (flash bwd recomputes in VMEM)
    bwd = (qb * 2 + kvb + ob) + (qb + kvb)
    return fwd, fwd + bwd


def project_cell(cell: dict, *, b_loc, h_loc, s, d, kv_loc, layers,
                 attn_passes=3.0, window=0, k_chunk=1024) -> dict:
    """attn_passes: 2 fwd (remat) + 1 bwd worth of traffic ~ fwd + fwd+bwd."""
    t_f, t_fb = attention_hlo_traffic(b_loc, h_loc, s, d, window=window,
                                      k_chunk=k_chunk)
    k_f, k_fb = kernel_boundary_traffic(b_loc, h_loc, s, d, kv_loc)
    # per layer: one fwd (live) + one fwd (remat) + one bwd
    hlo_total = layers * (t_f + t_fb)
    kern_total = layers * (k_f + k_fb)
    saved = hlo_total - kern_total
    adj = dict(cell)
    # the dry-run cells it adjusts describe TPU v5e chips
    hbm_bw = peak_for("TPU v5 lite").hbm_bytes_per_s
    adj["memory_s"] = cell["memory_s"] - saved / hbm_bw
    adj["per_device_bytes"] = cell["per_device_bytes"] - saved
    adj["attn_hlo_bytes"] = hlo_total
    adj["attn_kernel_bytes"] = kern_total
    terms = {"compute": adj["compute_s"], "memory": adj["memory_s"],
             "collective": adj["collective_s"]}
    adj["bottleneck"] = max(terms, key=terms.get)
    return adj


def main():
    with open("results/hillclimb.json") as f:
        hc = json.load(f)
    with open("results/dryrun.json") as f:
        base = json.load(f)

    cases = {
        # deepseek train: B=256/16, H=64/16, S=4096, d=128, KV=8/16->1(rep/2)
        "deepseek-67b|train_4k|pod16x16|pallas": (
            base["deepseek-67b|train_4k|pod16x16"],
            dict(b_loc=16, h_loc=4, s=4096, d=128, kv_loc=1, layers=95)),
        # qwen3 train on top of moeshard
        "qwen3-moe-235b-a22b|train_4k|pod16x16|moeshard+pallas": (
            hc["qwen3-moe-235b-a22b|train_4k|pod16x16|moeshard"],
            dict(b_loc=16, h_loc=4, s=4096, d=128, kv_loc=1, layers=94)),
        # gemma3 on top of localattn+sp: per-device q seq 4096/16, full heads
        "gemma3-1b|train_4k|pod16x16|localattn+sp+pallas": (
            hc["gemma3-1b|train_4k|pod16x16|localattn+sp"],
            dict(b_loc=16, h_loc=4, s=256, d=256, kv_loc=1, layers=26,
                 window=512)),
    }
    for key, (cell, kw) in cases.items():
        adj = project_cell(cell, **kw)
        hc[key] = adj
        print(f"[pallas] {key}: memory {cell['memory_s']:.1f}s -> "
              f"{adj['memory_s']:.1f}s (attn HLO {adj['attn_hlo_bytes']/1e9:.0f}GB"
              f" -> kernel {adj['attn_kernel_bytes']/1e9:.0f}GB); "
              f"bottleneck {adj['bottleneck']}")
    with open("results/hillclimb.json", "w") as f:
        json.dump(hc, f, indent=1)


if __name__ == "__main__":
    main()
