"""GQA-aware flash attention Pallas kernel (TPU target, interpret-validated).

Grid: (B*H, Sq/bq, Sk/bk), KV innermost; the (acc, m, l) online-softmax
state lives in VMEM scratch across the KV sweep.  KV heads are indexed
directly via the BlockSpec index map (kv = head // group) — no O(H/KV)
KV expansion in HBM, which is the dominant traffic saving vs the naive
path for GQA models (kv=1..8 vs 16-64 q heads on the assigned archs).

Supports causal masking and sliding windows (gemma3/hymba local layers).
(bq, bk) is the schedule: the NN+C autotuner's variant axis for attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(i, j, bq, bk, sk_orig, causal, window):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < sk_orig
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= q_pos - k_pos < window
    return ok


def _fa_fwd_kernel(scale, causal, window, bq, bk, sk_orig,
                   q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
    """Forward; also emits the row log-sum-exp the backward reads."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(i, j, bq, bk, sk_orig, causal, window), s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[None, :]


def _fa_bwd_dq_kernel(scale, causal, window, bq, bk, sk_orig,
                      q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref):
    """dq: grid (B*H, nq, nk), kv innermost; dq tile accumulates in VMEM."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    ok = _mask(i, j, bq, bk, sk_orig, causal, window)
    p = jnp.where(ok, jnp.exp(s - lse[:, None]), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(scale, causal, window, bq, bk, sk_orig,
                       q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc):
    """dk/dv: grid (B*H, nk, nq), q innermost; dk/dv tiles live in VMEM."""
    i = pl.program_id(2)           # q block (innermost)
    j = pl.program_id(1)           # kv block

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    ok = _mask(i, j, bq, bk, sk_orig, causal, window)
    p = jnp.where(ok, jnp.exp(s - lse[:, None]), 0.0)
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale

    @pl.when(i == pl.num_programs(2) - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "sk_orig", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=0, bq=256, bk=256,
                        sk_orig=0, interpret):
    """Returns (out [B,H,Sq,D], lse [B,H,1,Sq]) — forward with residuals.
    The row statistic keeps a unit axis so its block's last two dims
    (1, bq) are legal on the chip's (8, 128) tiling."""
    b, h, sq, d = q.shape
    _, kv, sk, _ = k.shape
    group = h // kv
    sk_orig = sk_orig or sk
    scale = d ** -0.5
    kernel = functools.partial(_fa_fwd_kernel, scale, causal, window, bq, bk,
                               sk_orig)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)),
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bh, i, j: (bh // h, bh % h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, i, j: (bh // h, (bh % h) // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, i, j: (bh // h, (bh % h) // group, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, d), lambda bh, i, j: (bh // h, bh % h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda bh, i, j: (bh // h, bh % h, 0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "sk_orig", "interpret"))
def flash_attention_bwd(q, k, v, do, lse, delta, *, causal=True, window=0,
                        bq=256, bk=256, sk_orig=0, interpret):
    """lse, delta: [B,H,1,Sq].  Returns (dq [B,H,Sq,D], dk, dv per-q-head
    [B,H,Sk,D]) — the caller group-sums dk/dv over GQA groups."""
    b, h, sq, d = q.shape
    _, kv, sk, _ = k.shape
    group = h // kv
    sk_orig = sk_orig or sk
    scale = d ** -0.5
    q_idx = lambda bh, i, j: (bh // h, bh % h, i, 0)
    kv_idx = lambda bh, i, j: (bh // h, (bh % h) // group, j, 0)
    row_idx = lambda bh, i, j: (bh // h, bh % h, 0, i)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale, causal, window, bq, bk,
                          sk_orig),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_idx),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bq, d), q_idx),
            pl.BlockSpec((1, 1, 1, bq), row_idx),
            pl.BlockSpec((1, 1, 1, bq), row_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_idx),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: swap grid so kv blocks are outer, q innermost
    q_idx2 = lambda bh, j, i: (bh // h, bh % h, i, 0)
    kv_idx2 = lambda bh, j, i: (bh // h, (bh % h) // group, j, 0)
    kvh_idx2 = lambda bh, j, i: (bh // h, bh % h, j, 0)
    row_idx2 = lambda bh, j, i: (bh // h, bh % h, 0, i)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale, causal, window, bq, bk,
                          sk_orig),
        out_shape=(jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), q.dtype)),
        grid=(b * h, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_idx2),
            pl.BlockSpec((1, 1, bk, d), kv_idx2),
            pl.BlockSpec((1, 1, bk, d), kv_idx2),
            pl.BlockSpec((1, 1, bq, d), q_idx2),
            pl.BlockSpec((1, 1, 1, bq), row_idx2),
            pl.BlockSpec((1, 1, 1, bq), row_idx2),
        ],
        out_specs=(pl.BlockSpec((1, 1, bk, d), kvh_idx2),
                   pl.BlockSpec((1, 1, bk, d), kvh_idx2)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
