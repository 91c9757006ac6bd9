"""The plain reference of a llama-style decoder, in float32.

Written from the published description (Llama; Yi, arXiv:2403.04652;
DeepSeek LLM, arXiv:2401.02954): token embedding; per layer RMSNorm,
grouped-query attention with rotary positions (the rotate-half form,
inverse frequencies ``theta ** (-2i / head_dim)``), a residual, RMSNorm,
a SwiGLU MLP and a residual; a final RMSNorm and an untied head.  It
imports nothing of the program and takes nothing the program made: it
draws its own weights from the seed (``weights.layer``/``weights.top``)
one layer at a time, after the program's copy is freed.

Matrix products run in float32 at ``Precision.HIGHEST``.  The control,
``precision="fp8"``, is the same computation with every weight matrix
quantised to float8 (e4m3, one scale per matrix) and the products in
bfloat16 with float32 accumulation: the next precision below the
configuration's bfloat16.

Sequences (and the positions read) are padded to power-of-two buckets;
the model is causal, so the padding cannot change the logits at real
positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.counting import Dims

RMS_EPS = 1e-6
Q_BLOCK = 256
VOCAB_BLOCK = 16384
HI = jax.lax.Precision.HIGHEST


def fp8(w):
    """Per-tensor float8 e4m3 quantisation, dequantised to bfloat16:
    ``(values, scale)``."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.bfloat16), scale


def _mm(x, w, spec: str, precision: str):
    """``einsum(spec, x, w)`` in the reference's or the control's
    arithmetic; ``x`` is float32, the result float32."""
    if precision == "fp8":
        wq, scale = fp8(w)
        out = jnp.einsum(spec, x.astype(jnp.bfloat16), wq,
                         preferred_element_type=jnp.float32)
        return out * scale
    return jnp.einsum(spec, x, w.astype(jnp.float32), precision=HI)


def _rms(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * scale.astype(jnp.float32)


def _rope(x, theta: float):
    """x: [S, H, D]; position p rotates pair (i, i + D/2) by
    ``p * theta ** (-2i / D)``."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention in float32.  q: [S, H, D]; k, v: [S, KV, D]."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = s // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, h, d)
    kpos = jnp.arange(s)

    def block(args):
        qi, i = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * d ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (qb, jnp.arange(nb)))
    return out.reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("theta", "precision"))
def _layer(x, w, *, theta: float, precision: str):
    """One decoder layer over ``x`` [S, d] (float32)."""
    h = _rms(x, w["norm1"]["scale"])
    a = w["attn"]
    q = _rope(_mm(h, a["wq"], "sd,dhk->shk", precision), theta)
    k = _rope(_mm(h, a["wk"], "sd,dhk->shk", precision), theta)
    v = _mm(h, a["wv"], "sd,dhk->shk", precision)
    x = x + _mm(_attention(q, k, v), a["wo"], "shk,hkd->sd", precision)
    h = _rms(x, w["norm2"]["scale"])
    m = w["mlp"]
    g = _mm(h, m["w_gate"], "sd,df->sf", precision)
    u = _mm(h, m["w_up"], "sd,df->sf", precision)
    return x + _mm(jax.nn.silu(g) * u, m["w_down"], "sf,fd->sd", precision)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_block(x, scale, table, *, precision: str):
    return _mm(_rms(x, scale), table, "sd,vd->sv", precision)


def bucket(n: int) -> int:
    """Padded length: a power of two, at least two blocks, so that a few
    programs (kept in the compilation cache) serve every length."""
    return max(2 * Q_BLOCK, 1 << max(n - 1, 1).bit_length())


def logits(arch: dict, seed: int, sequences: list, positions: list,
           precisions=("f32",)) -> dict:
    """Logits of each sequence at the given positions.

    ``sequences``: lists of token ids; ``positions``: for each sequence
    the positions whose next-token logits are wanted.  Returns
    ``{precision: [array [len(positions_i), vocab] float32, ...]}``.
    Weights are drawn once per layer and applied to every sequence and
    every precision before the next layer is drawn.
    """
    m = Dims.of(arch)
    key = weights.seed_key(seed)
    theta = float(arch["rope_theta"])
    table = weights.top(key, m, "embed")
    xs = {p: [_embed(table, jnp.asarray(
        list(s) + [0] * (bucket(len(s)) - len(s)), jnp.int32))
        for s in sequences] for p in precisions}
    del table
    for li in range(m.n_layers):
        w = weights.layer(key, m, li)
        for p in precisions:
            xs[p] = [_layer(x, w, theta=theta, precision=p) for x in xs[p]]
        del w
    scale = weights.top(key, m, "final_norm")
    table = weights.top(key, m, "unembed")
    out = {}
    for p in precisions:
        rows = []
        for x, pos in zip(xs[p], positions):
            padded = list(pos) + [pos[-1]] * (bucket(len(pos)) - len(pos))
            xr = x[jnp.asarray(padded, jnp.int32)]
            parts = [_head_block(xr, scale, table[v:v + VOCAB_BLOCK],
                                 precision=p)
                     for v in range(0, m.vocab_size, VOCAB_BLOCK)]
            rows.append(np.asarray(jnp.concatenate(parts, axis=1))[:len(pos)])
        out[p] = rows
    return out
