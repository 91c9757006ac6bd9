"""Blockwise ring attention over one mesh axis (sequence parallelism).

The sequence axis of q, k, v is sharded over ``axis_name``; each device
keeps its q block resident while k/v blocks rotate around the ring with
``jax.lax.ppermute``.  Per hop the device folds the visiting k/v block into
an online-softmax accumulator (the same update as ``attend_chunked``), so
peak memory is O(S/n) per device and the only collective is the neighbour
exchange.  Numerics match the dense reference ``models.attention.attend_full``
for causal, non-causal and sliding-window masks; uneven ``seq % n`` is
handled by padding the sequence and masking the pad keys.

The first hop processes the device's own (diagonal) block, which every query
can see under any supported mask — the running max is finite from step one,
so fully-masked later blocks contribute exact zeros.  Under a causal mask
those zero-contribution blocks are *skipped* outright: at hop ``step`` the
devices with ``idx < step`` hold a block that wrapped around the ring and
sits entirely in their causal future, so the whole online-softmax update is
guarded by a ``lax.cond`` (halving causal ring FLOPs) while the ppermute
rotation — a collective — still runs on every device every hop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import compat
from repro.dist.masking import NEG_INF, PAD_SENTINEL, mask_bias
from repro.dist.sharding import _axis_sizes, active_mesh


def _causal_skip_possible(step: int, n: int, s_loc: int,
                          q_offset: int) -> bool:
    """True when ring hop ``step`` presents a fully causally-masked k/v
    block to the devices with ``idx < step``: their block wrapped around
    the ring (src = idx - step + n), so its smallest key position
    ``src * s_loc`` exceeds their largest query position
    ``idx * s_loc + s_loc - 1 + q_offset`` — independent of idx, hence
    static per hop; ``idx`` only decides *which* devices skip (a lax.cond
    inside the SPMD body).  A window mask only removes further visibility,
    so the causal criterion stays safe with ``window > 0``."""
    return step > 0 and (n - step - 1) * s_loc >= q_offset


def ring_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                cache_index, *, mesh=None, axis_name: str = "model",
                window: int = 0, start=None, layer=None) -> jax.Array:
    """Decode-time ring attention over a sequence-sharded KV cache.

    q: [B,1,H,D]; caches: [B,KV,Smax,D], or with ``layer`` the stacked
    [L,B,KV,Smax,D] leaves of a layer scan, with ``cache_seq`` sharded over
    ``axis_name`` (``serve_rules(long_context=True)``).  A stacked cache
    enters the SPMD body whole and each device reads layer ``layer`` of its
    own shard there, so no layer is sliced out of the stack first.

    Unlike the prefill ring, the KV shards never move: each device
    computes grouped online-softmax *stats* (acc, m, l) over its resident
    shard and the tiny [B,KV,G]-shaped stats rotate around the ring
    instead of the multi-GB cache — per-step collective traffic is
    O(B*H*D), not O(Smax*KV*D/n).

    A shard whose keys are all masked for some row yields m = NEG_INF
    (finite, so exp(m - m) = 1, no NaN); its poisoned (acc, l) are
    annihilated by alpha = exp(NEG_INF - m_finite) = 0 when any visible
    shard folds in, and the shard holding ``cache_index`` is always
    visible.  Degenerates to ``attend_decode`` with no mesh, a 1-device
    ring, or a cache length the ring cannot split evenly.
    """
    if mesh is None:
        mesh = active_mesh()
    from repro.models.attention import attend_decode, layer_of
    b, one, h, d = q.shape
    kv, smax = k_cache.shape[-3], k_cache.shape[-2]
    sizes = _axis_sizes(mesh) if mesh is not None else {}
    n = sizes.get(axis_name, 1)
    if mesh is None or n <= 1 or smax % n != 0:
        return attend_decode(q, layer_of(k_cache, layer).astype(q.dtype),
                             layer_of(v_cache, layer).astype(q.dtype),
                             cache_index, window=window, start=start)
    g = h // kv
    s_loc = smax // n
    scale = d ** -0.5
    if start is None:
        start = jnp.zeros((b,), jnp.int32)   # pos >= 0 is vacuous
    cache_index = jnp.asarray(cache_index, jnp.int32)

    stacked = layer is not None
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32)
    kv_spec = P(*(None,) * (k_cache.ndim - 2), axis_name, None)
    rep4 = P(None, None, None, None)

    def ringd(q_loc, k_loc, v_loc, idx0, start_loc, layer_loc):
        li = layer_loc if stacked else None
        k_loc = layer_of(k_loc, li).astype(q_loc.dtype)
        v_loc = layer_of(v_loc, li).astype(q_loc.dtype)
        idx = jax.lax.axis_index(axis_name)
        pos = idx * s_loc + jnp.arange(s_loc)
        visible = (pos <= idx0)[None, :] & (pos[None, :] >= start_loc[:, None])
        if window > 0:
            visible = visible & (pos > idx0 - window)[None, :]
        q0 = q_loc[:, 0].reshape(b, kv, g, d)
        sc = jnp.einsum("bkgd,bktd->bkgt", q0, k_loc
                        ).astype(jnp.float32) * scale
        sc = jnp.where(visible[:, None, None, :], sc, NEG_INF)
        m = sc.max(axis=-1)                              # [B,KV,G]
        p = jnp.exp(sc - m[..., None])
        l = p.sum(axis=-1)
        acc = jnp.einsum("bkgt,bktd->bkgd", p,
                         v_loc.astype(jnp.float32))

        def merge(a, b_):
            acc1, m1, l1 = a
            acc2, m2, l2 = b_
            m_new = jnp.maximum(m1, m2)
            a1 = jnp.exp(m1 - m_new)
            a2 = jnp.exp(m2 - m_new)
            return (acc1 * a1[..., None] + acc2 * a2[..., None],
                    m_new, l1 * a1 + l2 * a2)

        perm = [(j, (j + 1) % n) for j in range(n)]
        run, vis = (acc, m, l), (acc, m, l)
        for _ in range(1, n):
            vis = jax.tree.map(
                lambda t: jax.lax.ppermute(t, axis_name, perm), vis)
            run = merge(run, vis)
        acc, m, l = run
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,KV,G,D]
        return out.reshape(b, 1, h, d).astype(q_loc.dtype)

    return compat.shard_map(
        ringd, mesh,
        in_specs=(rep4, kv_spec, kv_spec, P(), P(None), P()),
        out_specs=rep4)(q, k_cache, v_cache, cache_index, start, layer)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh=None, axis_name: str = "model", causal: bool = True,
                   window: int = 0, q_offset: int = 0) -> jax.Array:
    """q, k, v: [B, S, H, D] (kv heads pre-expanded) -> [B, S, H, D].

    ``mesh`` defaults to the active mesh; on a 1-device ring (or no mesh at
    all) this degenerates to the chunked dense path, so callers can use it
    unconditionally.
    """
    if mesh is None:
        mesh = active_mesh()
    b, s, h, d = q.shape
    sizes = _axis_sizes(mesh) if mesh is not None else {}
    n = sizes.get(axis_name, 1)
    if mesh is None or n <= 1:
        from repro.models.attention import attend_chunked
        return attend_chunked(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)

    pad = (-s) % n
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s_loc = (s + pad) // n
    scale = d ** -0.5

    # shard batch over whatever data axes the mesh has (when divisible)
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in batch_axes:
        dp *= sizes[a]
    b_spec = None
    if batch_axes and b % dp == 0:
        b_spec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    spec = P(b_spec, axis_name, None, None)

    def ring(q_loc, k_loc, v_loc):
        idx = jax.lax.axis_index(axis_name)
        bl = q_loc.shape[0]
        offs = jnp.arange(s_loc)
        q_pos = idx * s_loc + offs + q_offset
        acc = jnp.zeros((bl, h, s_loc, d), jnp.float32)
        m = jnp.full((bl, h, s_loc), NEG_INF, jnp.float32)
        l = jnp.zeros((bl, h, s_loc), jnp.float32)
        k_cur, v_cur = k_loc, v_loc
        perm = [(j, (j + 1) % n) for j in range(n)]
        for step in range(n):
            src = (idx - step) % n            # block index k_cur came from
            k_pos = src * s_loc + offs
            k_pos = jnp.where(k_pos < s, k_pos, PAD_SENTINEL + k_pos)

            def fold(acc, m, l, _k=k_cur, _v=v_cur, _pos=k_pos):
                sc = jnp.einsum("bshd,bthd->bhst", q_loc, _k
                                ).astype(jnp.float32) * scale
                sc = sc + mask_bias(q_pos, _pos, causal, window)[None, None]
                m_new = jnp.maximum(m, sc.max(axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(sc - m_new[..., None])
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhst,bthd->bhsd", p.astype(q_loc.dtype), _v
                ).astype(jnp.float32)
                return acc_new, m_new, l_new

            if causal and _causal_skip_possible(step, n, s_loc, q_offset):
                # fully-masked blocks contribute exact zeros — skip the
                # whole update on the devices holding one; the rotation
                # below still runs everywhere (ppermute is collective)
                acc, m, l = jax.lax.cond(
                    idx >= step, fold, lambda acc, m, l: (acc, m, l),
                    acc, m, l)
            else:
                acc, m, l = fold(acc, m, l)
            if step != n - 1:
                k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
                v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q_loc.dtype)

    out = compat.shard_map(ring, mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)(q, k, v)
    return out[:, :s] if pad else out
