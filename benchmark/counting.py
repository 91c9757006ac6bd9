"""Operations and bytes that the algorithms need, counted from shapes.

These are the numerators of every roofline and MFU share.  They count
what the mathematics requires, not what a compiled implementation does:
a decode step reads each weight once and only the live part of the KV
cache (each active slot's own context), and causal attention does half
of the square.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths of a llama-style decoder (GQA, SwiGLU, untied head)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    param_bytes: int = 2          # bfloat16 weights
    cache_bytes: int = 2          # bfloat16 KV cache

    @classmethod
    def of(cls, arch: dict) -> "Dims":
        return cls(**{f.name: arch[f.name] for f in dataclasses.fields(cls)
                      if f.name in arch})


def layer_matmul_params(m: Dims) -> int:
    """q, k, v, o and gate, up, down of one layer."""
    attn = m.d_model * m.head_dim * (2 * m.n_heads + 2 * m.n_kv_heads)
    return attn + 3 * m.d_model * m.d_ff


def matmul_params(m: Dims) -> int:
    """Every weight a token passes through: the layers and the head."""
    return m.n_layers * layer_matmul_params(m) + m.vocab_size * m.d_model


def kv_bytes_per_token(m: Dims) -> int:
    return 2 * m.n_layers * m.n_kv_heads * m.head_dim * m.cache_bytes


def serve_step(m: Dims, spans) -> tuple:
    """``(flops, bytes)`` of one engine step over its riders, each given as
    ``(before, after)``: its positions in the cache before and after the
    step, so it processed the positions ``before + 1 .. after``, each
    attending to itself and every position before it.  Bytes: every
    weight once (the embedding only for the rows gathered), each rider's
    live KV up to ``after`` read, and its new positions' KV written."""
    spans = list(spans)
    tokens = sum(a - b for b, a in spans)
    seen = sum((a * (a + 1) - b * (b + 1)) / 2.0 for b, a in spans)
    flops = 2.0 * matmul_params(m) * tokens \
        + 4.0 * m.n_layers * m.n_heads * m.head_dim * seen
    weights = (matmul_params(m) + tokens * m.d_model
               + (2 * m.n_layers + 1) * m.d_model) * m.param_bytes
    kv = sum(2 * a - b for b, a in spans) * kv_bytes_per_token(m)
    return flops, float(weights + kv)


def matmul(mm: int, n: int, k: int, itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of ``[m, k] @ [k, n]``: both operands read once
    and the result written once."""
    return 2.0 * mm * n * k, float((mm * k + k * n + mm * n) * itemsize)


def causal_attention(b: int, s: int, h: int, d: int,
                     itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of causal attention over ``[b, s, h, d]`` q, k, v
    with equal head counts: q.k and p.v over the s(s+1)/2 visible pairs;
    q, k, v read once and the output written once."""
    pairs = s * (s + 1) / 2.0
    return 4.0 * b * h * d * pairs, float(4 * b * s * h * d * itemsize)
