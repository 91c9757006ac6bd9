"""Public flash-attention op: pads sequence dims, dispatches kernel/oracle.

``attention`` is fully differentiable: a ``jax.custom_vjp`` routes the
backward through the two-pass flash backward kernels (dq sweep + dkv
sweep with the forward's saved log-sum-exp), so neither forward nor
backward ever materialises the [Sq, Sk] score matrix in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import Aval, resolve_interpret
from repro.kernels.flash_attention import flash_attention as _kernel
from repro.kernels.flash_attention import ref as _ref


def abstract_params(q, k, v) -> dict:
    """Predictor params from avals (shape-only).  This entry point is
    [B, H, S, D]; the runtime registry's ``flash_attention`` variant set is
    built over ``models.attention`` ([B, S, H, D]) and carries its own hook
    with the same param keys."""
    b, h, s, d = q.shape
    return {"b": int(b), "h": int(h), "s": int(s), "d": int(d)}


def out_aval(q, k, v) -> Aval:
    return Aval(tuple(q.shape), q.dtype)


def _pad(q, k, v, bq, bk):
    sq, sk = q.shape[2], k.shape[2]
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    return q, k, v, sq, sk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention(q, k, v, causal, window, bq, bk, interpret):
    qp, kp, vp, sq, sk = _pad(q, k, v, bq, bk)
    out, _ = _kernel.flash_attention_fwd(
        qp, kp, vp, causal=causal, window=window, bq=bq, bk=bk,
        sk_orig=sk, interpret=interpret)
    return out[:, :, :sq]


def _attention_fwd(q, k, v, causal, window, bq, bk, interpret):
    qp, kp, vp, sq, sk = _pad(q, k, v, bq, bk)
    out, lse = _kernel.flash_attention_fwd(
        qp, kp, vp, causal=causal, window=window, bq=bq, bk=bk,
        sk_orig=sk, interpret=interpret)
    return out[:, :, :sq], (qp, kp, vp, out, lse, sq, sk)


def _attention_bwd(causal, window, bq, bk, interpret, res, dout):
    qp, kp, vp, out, lse, sq, sk = res
    kv = kp.shape[1]
    h = qp.shape[1]
    dop = jnp.pad(dout, ((0, 0), (0, 0), (0, qp.shape[2] - sq), (0, 0)))
    # delta_i = rowsum(do * o) (cheap, jnp)
    delta = jnp.sum(dop.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    dq, dkh, dvh = _kernel.flash_attention_bwd(
        qp, kp, vp, dop, lse, delta, causal=causal, window=window,
        bq=bq, bk=bk, sk_orig=sk, interpret=interpret)
    # GQA: sum the per-q-head dk/dv over each group
    b, _, skp, d = dkh.shape
    g = h // kv
    dk = dkh.reshape(b, kv, g, skp, d).sum(axis=2).astype(kp.dtype)
    dv = dvh.reshape(b, kv, g, skp, d).sum(axis=2).astype(vp.dtype)
    return (dq[:, :, :sq].astype(qp.dtype), dk[:, :, :sk], dv[:, :, :sk])


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0,
              bq: int = 256, bk: int = 256,
              use_kernel: bool = True,
              interpret: Optional[bool] = None) -> jax.Array:
    if not use_kernel:
        return _ref.attention(q, k, v, causal=causal, window=window)
    # resolve here: interpret is a static nondiff arg of the custom_vjp
    interpret = resolve_interpret(interpret)
    sq = q.shape[2]
    bq = min(bq, sq) if sq % min(bq, sq) == 0 else bq
    return _attention(q, k, v, causal, window, bq, bk, interpret)
