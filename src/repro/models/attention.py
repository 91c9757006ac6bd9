"""GQA attention: flash-style chunked softmax, sliding windows, KV cache.

Three execution paths share one set of projection weights:

  * ``attend_full``    — O(S^2) reference (small seqs / tests).
  * ``attend_chunked`` — lax.scan over KV chunks with online softmax and a
    remat'ed body: peak activation O(S * q_chunk) instead of O(S^2).  This is
    the pure-JAX adaptation of flash attention; the Pallas kernel in
    ``repro/kernels/flash_attention`` is the TPU hot-path variant.
  * ``attend_decode``  — one query position against a (possibly
    sequence-sharded) KV cache with masked online softmax.

The decode cache holds K and V as ``[B, KV, Smax, D]``: each (slot, kv-head)
pair is one ``[Smax, D]`` matrix, the operand the two decode dots batch over,
so the cache is read without a relayout.  A layer stack keeps one such leaf
per layer kind with a leading layer axis, ``[L, B, KV, Smax, D]``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ArchConfig
from repro.dist.masking import (NEG_INF, PAD_SENTINEL as _PAD_SENTINEL,
                                mask_bias as _mask_bias)
from repro.dist.sharding import active_mesh, constrain, per_shard
from repro.models.layers import rope
from repro.models.module import ParamSpec

# Logical axes of a decode cache leaf, [B, KV, Smax, D]
KV_CACHE_AXES = ("batch", "kv_heads", "cache_seq", "head_dim")


def attention_spec(cfg: ArchConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((d, h, hd), jnp.float32, ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), jnp.float32, ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), jnp.float32, ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), jnp.float32, ("heads", "head_dim", "embed"),
                        fan_in_axes=(0, 1)),
    }


def _project_qkv(cfg, params, x, kv_src=None):
    dtype = x.dtype
    kv_src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("btd,dhk->bthk", kv_src, params["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", kv_src, params["wv"].astype(dtype))
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """[B,T,KV,D] -> [B,T,H,D] by repeating each kv head H/KV times."""
    b, t, kv, d = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    return jnp.repeat(k, rep, axis=2)


def attend_full(q, k, v, *, causal: bool, window: int = 0,
                q_offset: int = 0) -> jax.Array:
    """Naive reference attention.  q:[B,Sq,H,D] k,v:[B,Sk,H,D]."""
    scale = q.shape[-1] ** -0.5
    sq, sk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    bias = _mask_bias(jnp.arange(sq) + q_offset, jnp.arange(sk), causal, window)
    probs = jax.nn.softmax(scores + bias[None, None], axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(q.dtype), v)
    return out


def _chunk_body(scale, causal, window, q, q_pos, carry, kv_chunk):
    """Online-softmax update for one KV chunk (remat'ed in the scan)."""
    acc, m, l = carry
    k_c, v_c, k_pos = kv_chunk
    s = jnp.einsum("bshd,bthd->bhst", q, k_c).astype(jnp.float32) * scale
    s = s + _mask_bias(q_pos, k_pos, causal, window)[None, None]
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhst,bthd->bhsd", p.astype(q.dtype), v_c).astype(jnp.float32)
    return (acc, m_new, l), None


def _attend_kv_scan(q, k_r, v_r, p_r, q_pos, *, causal, window) -> jax.Array:
    """Online-softmax over pre-chunked KV.  q:[B,Sq,H,D]; k_r:[N,B,C,H,D]."""
    b, sq, h, d = q.shape
    scale = d ** -0.5
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    body = functools.partial(_chunk_body, scale, causal, window, q, q_pos)
    (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, l0),
                                  (k_r, v_r, p_r))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attend_chunked(q, k, v, *, causal: bool, window: int = 0,
                   k_chunk: int = 1024, q_chunk: int = 512,
                   q_offset: int = 0) -> jax.Array:
    """Flash-style attention: q-block x kv-chunk tiling, online softmax.

    The outer ``lax.map`` over q blocks x inner ``lax.scan`` over KV chunks
    mirrors the VMEM tiling of the Pallas flash kernel; peak score-matrix
    memory is O(B*H*q_chunk*k_chunk) instead of O(B*H*Sq*Sk)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sk <= k_chunk:
        return attend_full(q, k, v, causal=causal, window=window, q_offset=q_offset)
    n_chunks = -(-sk // k_chunk)
    pad = n_chunks * k_chunk - sk
    k_pos = jnp.arange(n_chunks * k_chunk)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.where(k_pos < sk, k_pos, _PAD_SENTINEL + k_pos)
    k_r = k.reshape(b, n_chunks, k_chunk, h, d).transpose(1, 0, 2, 3, 4)
    v_r = v.reshape(b, n_chunks, k_chunk, h, d).transpose(1, 0, 2, 3, 4)
    p_r = k_pos.reshape(n_chunks, k_chunk)

    if sq <= q_chunk:
        return _attend_kv_scan(q, k_r, v_r, p_r, jnp.arange(sq) + q_offset,
                               causal=causal, window=window)
    nq = -(-sq // q_chunk)
    qpad = nq * q_chunk - sq
    q_pos = jnp.arange(nq * q_chunk) + q_offset
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    q_b = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    qp_b = q_pos.reshape(nq, q_chunk)

    def one_block(args):
        qb, qpb = args
        return _attend_kv_scan(qb, k_r, v_r, p_r, qpb,
                               causal=causal, window=window)

    out = jax.lax.map(one_block, (q_b, qp_b))        # [nq,B,q_chunk,H,D]
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq]


def attend_local(q, k, v, *, window: int, q_offset: int = 0) -> jax.Array:
    """Block-banded sliding-window attention: O(S*2w) compute/memory.

    Queries are blocked at the window size; block i attends only blocks
    {i-1, i} (every key within (p-w, p] lives there).  This is the §Perf
    optimisation for gemma3/hymba local layers — the baseline computes the
    full S^2 score matrix and masks 1-2w/S of it away."""
    b, s, h, d = q.shape
    w = window
    nb = -(-s // w)
    pad = nb * w - s
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    qb = qp.reshape(b, nb, w, h, d)
    kb = kp.reshape(b, nb, w, h, d)
    vb = vp.reshape(b, nb, w, h, d)
    # previous block (block -1 is zeros, masked out by positions)
    k_prev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    v_prev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([k_prev, kb], axis=2)          # [b,nb,2w,h,d]
    v2 = jnp.concatenate([v_prev, vb], axis=2)
    scale = d ** -0.5
    s_ = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, k2).astype(jnp.float32) * scale
    q_pos = (jnp.arange(nb * w).reshape(nb, w) + q_offset)
    k_pos = q_pos[:, :1] // w * w - w + jnp.arange(2 * w)[None, :]
    valid = (k_pos >= 0) & (k_pos < s + q_offset)
    ok = (k_pos[:, None, :] <= q_pos[:, :, None]) \
        & (q_pos[:, :, None] - k_pos[:, None, :] < w) \
        & valid[:, None, :]
    s_ = jnp.where(ok[None, :, None], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(q.dtype), v2)
    return out.reshape(b, nb * w, h, d)[:, :s]


def attend_decode(q, k_cache, v_cache, cache_index, *, window: int = 0,
                  start=None) -> jax.Array:
    """Single-position decode.  q:[B,1,H,D]; caches:[B,KV,Smax,D].

    GQA is computed in *grouped* form (no KV expansion: the cache is the
    dominant HBM traffic at decode and must be read exactly once).  The
    cache sequence axis is sharded (serve_rules: 'cache_seq' -> model); q is
    constrained to replicated heads ('heads_act') so the distributed softmax
    reduces tiny [B,H] stats over the mesh instead of resharding the
    multi-GB cache (context-parallel decode)."""
    b, one, h, d = q.shape
    kv, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = d ** -0.5
    pos = jnp.arange(smax)
    visible = (pos <= cache_index)[None, :]
    if window > 0:
        visible = visible & (pos > cache_index - window)[None, :]
    if start is not None:
        # continuous batching: slot b was admitted at start[b]; anything
        # before that is a previous tenant's stale cache — mask it
        visible = visible & (pos[None, :] >= start[:, None])
    q = constrain(q, "batch", "seq", "heads_act", "head_dim")
    qg = q.reshape(b, one, kv, g, d)
    s = jnp.einsum("bikgd,bktd->bkgit", qg, k_cache).astype(jnp.float32) * scale
    s = constrain(s, "batch", "kv_heads_act", None, "seq", "cache_seq")
    s = jnp.where(visible[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgit,bktd->bikgd", p.astype(q.dtype), v_cache)
    out = out.reshape(b, one, h, d)
    return constrain(out, "batch", "seq", "heads_act", "head_dim")


def attention(cfg: ArchConfig, params: dict, x: jax.Array, *,
              causal: bool = True, window: int = 0,
              positions: Optional[jax.Array] = None,
              use_rope: bool = True,
              kv_src: Optional[jax.Array] = None,
              k_chunk: int = 1024, return_kv: bool = False,
              local_block: bool = False, ring: bool = False):
    """Full-sequence attention (train / prefill).  Cross-attn via kv_src.

    With ``return_kv`` also returns the post-rope (k, v) in cache layout
    [B,KV,S,D] so prefill can populate the decode cache.  ``local_block``
    switches windowed layers to the O(S*2w) banded path (§Perf)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x, kv_src)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        kv_pos = positions if kv_src is None else jnp.arange(k.shape[1])[None, :]
        k = rope(k, kv_pos, cfg.rope_theta)
    kv = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    k = _expand_kv(k, cfg.n_heads)
    v = _expand_kv(v, cfg.n_heads)
    if local_block and window > 0 and causal and s > window:
        out = attend_local(q, k, v, window=window)
    elif ring and kv_src is None:
        from repro.dist.ring_attention import ring_attention
        from repro.dist.sharding import active_mesh
        mesh = active_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and s % dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 0:
            out = ring_attention(q, k, v, mesh=mesh, axis_name="model",
                                 causal=causal, window=window)
        else:
            out = attend_chunked(q, k, v, causal=causal, window=window,
                                 k_chunk=k_chunk)
    else:
        out = attend_chunked(q, k, v, causal=causal, window=window,
                             k_chunk=k_chunk)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshd,hdk->bsk", out, params["wo"].astype(x.dtype))
    y = constrain(y, "batch", "seq", "embed")
    if return_kv:
        return y, kv
    return y


def write_token(cache: jax.Array, new: jax.Array, cache_index,
                layer=None) -> jax.Array:
    """Write one position's K or V, ``new`` [B,1,KV,D], into ``cache`` at
    ``cache_index``: a [B,KV,Smax,D] leaf, or layer ``layer`` of a stacked
    [L,B,KV,Smax,D] one.  The update has sequence extent 1, so under a
    donated cache (or a while-loop carry) the write is in place.

    The written cache is pinned to the layout the device stores it in
    (``stored_layout``): left free, XLA lays a scan's carry out like the
    projection that makes the token ([B, D] minor), and then copies the
    whole stack into that layout and back every step, and each layer out
    again for the dots.  Under a mesh the pin is set on each device's
    shard: the partitioner cannot split a layout constraint and would
    gather the stack for it."""
    upd = new.astype(cache.dtype).transpose(0, 2, 1, 3)       # [B,KV,1,D]
    zero = jnp.zeros((), jnp.int32)
    at = (zero, zero, jnp.asarray(cache_index, jnp.int32), zero)
    if layer is None:
        out = jax.lax.dynamic_update_slice(cache, upd, at)
    else:
        out = jax.lax.dynamic_update_slice(
            cache, upd[None], (jnp.asarray(layer, jnp.int32),) + at)
    return _pin_stored(out)


def write_rows(cache: jax.Array, new: jax.Array, slot, row) -> jax.Array:
    """Write one request's K or V, ``new`` [1,KV,P,D], into batch slot
    ``slot`` of ``cache`` at rows [row, row + P): a [B,KV,Smax,D] leaf, or
    every layer of a stacked [L,B,KV,Smax,D] one (``new`` then [L,1,KV,P,D]).
    One update per leaf, in place under a donated cache, pinned to the
    stored layout as ``write_token`` pins it.  ``dynamic_update_slice``
    clamps a block that would run past ``Smax``: callers keep
    ``row + P <= Smax``."""
    zero = jnp.zeros((), jnp.int32)
    at = (zero,) * (cache.ndim - 4) + (
        jnp.asarray(slot, jnp.int32), zero, jnp.asarray(row, jnp.int32), zero)
    return _pin_stored(jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), at))


def _pin_stored(cache: jax.Array) -> jax.Array:
    """``cache`` pinned to ``stored_layout``, on each device's shard."""
    return per_shard(lambda c: with_layout_constraint(c, stored_layout(c)),
                     cache, *("layers",) * (cache.ndim - 4), *KV_CACHE_AXES)


def layout_device():
    """The device whose storage layouts a trace assumes: the active mesh's,
    else the default device."""
    mesh = active_mesh()
    return mesh.devices.flat[0] if mesh is not None else jax.devices()[0]


def stored_layout(x: jax.Array) -> Layout:
    """The layout ``layout_device()`` stores an array of ``x``'s shape in,
    which a donated argument arrives in and its result leaves in.  On a TPU
    v5e a cache leaf [.., Smax, D] is row-major for D = 128 and 256 but
    sequence-minor for D = 64."""
    dev = layout_device()
    stored = dev.client.get_default_layout(x.dtype, x.shape, dev)
    return Layout(Layout.from_pjrt_layout(stored).major_to_minor)


def layer_of(cache: jax.Array, layer=None) -> jax.Array:
    """Layer ``layer`` of a stacked cache leaf (the leaf itself when
    ``layer`` is None)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def attention_decode_step(cfg: ArchConfig, params: dict, x: jax.Array,
                          cache: dict, cache_index: jax.Array, *,
                          window: int = 0, use_rope: bool = True,
                          update_cache: bool = True, start=None,
                          stream_kv: bool = False,
                          layer=None) -> tuple[jax.Array, dict]:
    """One decode step.  x:[B,1,d]; cache: {"k","v"}: [B,KV,Smax,D], or
    with ``layer`` the stacked [L,B,KV,Smax,D] leaves of a layer scan, of
    which this step writes and reads layer ``layer`` alone.  The new token
    is written before the read, so the attention sees position
    ``cache_index``; the returned cache is the written one (``cache``
    itself without ``update_cache``: cross-attention reads only).

    ``stream_kv`` routes the cache read through the decode ring
    (``dist.ring_attention.ring_decode``): with ``serve_rules(
    long_context=True)`` the ``cache_seq`` axis stays resident per device
    and only softmax stats travel; without a mesh it falls back to the
    dense ``attend_decode`` path unchanged."""
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    # Two-stage constraint: first pin the projections to the weight sharding
    # (so SPMD computes them locally per TP rank), THEN regather the tiny
    # [B,1,H,D] activations to replicated for the cache-sharded attention.
    # A single replicated constraint makes XLA all-gather the multi-MB
    # weights per layer instead of the KB-scale activations.
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    q = constrain(q, "batch", "seq", "heads_act", "head_dim")
    k_new = constrain(k_new, "batch", "seq", "kv_heads", "head_dim")
    k_new = constrain(k_new, "batch", "seq", "kv_heads_act", "head_dim")
    v_new = constrain(v_new, "batch", "seq", "kv_heads", "head_dim")
    v_new = constrain(v_new, "batch", "seq", "kv_heads_act", "head_dim")
    pos = jnp.full((x.shape[0], 1), cache_index, jnp.int32)
    if start is not None:
        pos = pos - start[:, None]        # request-local rope positions
    if use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k_new = rope(k_new, pos, cfg.rope_theta)
    if update_cache:
        cache = {"k": write_token(cache["k"], k_new, cache_index, layer),
                 "v": write_token(cache["v"], v_new, cache_index, layer)}
    if stream_kv:
        from repro.dist.ring_attention import ring_decode
        out = ring_decode(q, cache["k"], cache["v"], cache_index,
                          window=window, start=start, layer=layer)
    else:
        out = attend_decode(q, layer_of(cache["k"], layer).astype(dtype),
                            layer_of(cache["v"], layer).astype(dtype),
                            cache_index, window=window, start=start)
    y = jnp.einsum("bshd,hdk->bsk", out.astype(dtype), params["wo"].astype(dtype))
    return y, cache
