"""Model assembly: pattern-scanned layer stacks for all 10 architectures.

Layers are grouped into *periods* (one repetition of ``cfg.layer_pattern``);
full periods are ``lax.scan``-ned over stacked params (small HLO, one trace
per unique block kind) with a remat'ed body; the remainder (e.g. gemma3's
26 = 4*6 + 2) runs unrolled as the "tail".  The same structure drives both
``forward`` (train/prefill) and ``decode_step`` (KV-cache/state decode).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import apply_norm, mlp, mlp_spec, norm_spec
from repro.models.module import ParamSpec, stack_tree

# Positional KV leaves of a block's decode cache, [B, KV, Smax, D] (the
# encoder's length for the cross-attention pair).  Under the layer scan they
# stay stacked: a step writes one token into its layer and reads that layer
# in place.  Every other leaf is recurrent state, replaced whole each step.
KV_LEAVES = frozenset({"k", "v", "xk", "xv"})

# ---------------------------------------------------------------------------
# Per-block param specs
# ---------------------------------------------------------------------------

def block_spec(cfg: ArchConfig, kind: str, cross: bool = False) -> dict:
    d = cfg.d_model
    if kind == "mlstm":
        return xlstm_mod.mlstm_spec(cfg)
    if kind == "slstm":
        return xlstm_mod.slstm_spec(cfg)
    spec: dict[str, Any] = {
        "norm1": norm_spec(cfg.norm_kind, d),
        "attn": attn.attention_spec(cfg),
    }
    if cross:
        spec["norm_x"] = norm_spec(cfg.norm_kind, d)
        spec["cross"] = attn.attention_spec(cfg, cross=True)
    if kind == "hybrid":
        di = d
        spec["ssm_in"] = ParamSpec((d, di), jnp.float32, ("embed", "mlp"))
        spec["ssm"] = ssm_mod.ssm_spec(cfg, di)
        spec["ssm_out"] = ParamSpec((di, d), jnp.float32, ("mlp", "embed"))
        spec["fuse_attn_norm"] = norm_spec("rmsnorm", d)
        spec["fuse_ssm_norm"] = norm_spec("rmsnorm", d)
    if kind == "moe":
        spec["norm2"] = norm_spec(cfg.norm_kind, d)
        spec["moe"] = moe_mod.moe_spec(cfg)
    elif cfg.has_mlp:
        spec["norm2"] = norm_spec(cfg.norm_kind, d)
        spec["mlp"] = mlp_spec(cfg.mlp_kind, d, cfg.d_ff)
    return spec


def block_cache_spec(cfg: ArchConfig, kind: str, batch: int, max_seq: int,
                     cache_dtype=jnp.bfloat16, cross_len: int = 0) -> dict:
    """Decode-state declaration for one block (ParamSpec tree)."""
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    d = cfg.d_model
    if kind == "mlstm":
        di = 2 * d
        dh = di // cfg.n_heads
        return {"C": ParamSpec((batch, cfg.n_heads, dh, dh), jnp.float32,
                               ("batch", "heads", "head_dim", "head_dim"), init="zeros"),
                "n": ParamSpec((batch, cfg.n_heads, dh), jnp.float32,
                               ("batch", "heads", "head_dim"), init="zeros"),
                "m": ParamSpec((batch, cfg.n_heads), jnp.float32,
                               ("batch", "heads"), init="zeros")}
    if kind == "slstm":
        leaf = ParamSpec((batch, d), jnp.float32, ("batch", "embed"), init="zeros")
        return {"c": leaf, "n": leaf, "m": leaf, "h": leaf}
    # attention KV cache, one [max_seq, head_dim] matrix per (slot, kv head)
    kv_leaf = ParamSpec((batch, kv, max_seq, hd), cache_dtype,
                        attn.KV_CACHE_AXES, init="zeros")
    cache = {"k": kv_leaf, "v": kv_leaf}
    if kind == "hybrid":
        cache["h_ssm"] = ParamSpec((batch, d, cfg.ssm_state), jnp.float32,
                                   ("batch", "mlp", None), init="zeros")
    if cross_len:
        cross_leaf = ParamSpec((batch, kv, cross_len, hd), cache_dtype,
                               ("batch", "kv_heads", None, "head_dim"), init="zeros")
        cache["xk"] = cross_leaf
        cache["xv"] = cross_leaf
    return cache


# ---------------------------------------------------------------------------
# Per-block forward / decode
# ---------------------------------------------------------------------------

def block_forward(cfg: ArchConfig, kind: str, params: dict, x: jax.Array, *,
                  causal: bool = True, memory: Optional[jax.Array] = None,
                  k_chunk: int = 1024, local_block: bool = False,
                  ring: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (x_out, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    use_rope = cfg.positional == "rope"
    if kind == "mlstm":
        y, _ = xlstm_mod.mlstm_apply(cfg, params, x)
        return x + y, aux
    if kind == "slstm":
        y, _ = xlstm_mod.slstm_apply(cfg, params, x)
        return x + y, aux

    window = cfg.sliding_window if kind in ("local", "hybrid") else 0
    h = apply_norm(cfg.norm_kind, params["norm1"], x, impl=cfg.norm_impl)
    a = attn.attention(cfg, params["attn"], h, causal=causal, window=window,
                       use_rope=use_rope, k_chunk=k_chunk,
                       local_block=local_block, ring=ring)
    if kind == "hybrid":
        u = jnp.einsum("bsd,de->bse", h, params["ssm_in"].astype(x.dtype))
        s_out, _ = ssm_mod.ssm_apply(params["ssm"], u)
        s_out = jnp.einsum("bse,ed->bsd", s_out, params["ssm_out"].astype(x.dtype))
        a = 0.5 * (apply_norm("rmsnorm", params["fuse_attn_norm"], a, impl=cfg.norm_impl)
                   + apply_norm("rmsnorm", params["fuse_ssm_norm"], s_out, impl=cfg.norm_impl))
    x = x + a
    if memory is not None and "cross" in params:
        hx = apply_norm(cfg.norm_kind, params["norm_x"], x, impl=cfg.norm_impl)
        cx = attn.attention(cfg, params["cross"], hx, causal=False,
                            use_rope=False, kv_src=memory, k_chunk=k_chunk)
        x = x + cx
    if kind == "moe":
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        y, aux = moe_mod.moe_apply(cfg, params["moe"], h2)
        x = x + y
    elif cfg.has_mlp:
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        x = x + mlp(cfg.mlp_kind, params["mlp"], h2)
    return x, aux


def block_prefill(cfg: ArchConfig, kind: str, params: dict, x: jax.Array, *,
                  max_seq: int, cache_dtype=jnp.bfloat16,
                  memory: Optional[jax.Array] = None,
                  k_chunk: int = 1024) -> tuple[jax.Array, dict]:
    """Forward pass that also builds this block's decode cache."""
    s = x.shape[1]
    use_rope = cfg.positional == "rope"

    def pad_seq(a):
        return jnp.pad(a.astype(cache_dtype),
                       ((0, 0), (0, 0), (0, max_seq - s), (0, 0)))

    if kind == "mlstm":
        y, (C, n, m) = xlstm_mod.mlstm_apply(cfg, params, x)
        return x + y, {"C": C, "n": n, "m": m}
    if kind == "slstm":
        y, (c, n, m, hh) = xlstm_mod.slstm_apply(cfg, params, x)
        return x + y, {"c": c, "n": n, "m": m, "h": hh}

    window = cfg.sliding_window if kind in ("local", "hybrid") else 0
    h = apply_norm(cfg.norm_kind, params["norm1"], x, impl=cfg.norm_impl)
    a, (k, v) = attn.attention(cfg, params["attn"], h, causal=True,
                               window=window, use_rope=use_rope,
                               k_chunk=k_chunk, return_kv=True)
    cache = {"k": pad_seq(k), "v": pad_seq(v)}
    if kind == "hybrid":
        u = jnp.einsum("bsd,de->bse", h, params["ssm_in"].astype(x.dtype))
        s_out, h_ssm = ssm_mod.ssm_apply(params["ssm"], u)
        s_out = jnp.einsum("bse,ed->bsd", s_out, params["ssm_out"].astype(x.dtype))
        a = 0.5 * (apply_norm("rmsnorm", params["fuse_attn_norm"], a, impl=cfg.norm_impl)
                   + apply_norm("rmsnorm", params["fuse_ssm_norm"], s_out, impl=cfg.norm_impl))
        cache["h_ssm"] = h_ssm
    x = x + a
    if memory is not None and "cross" in params:
        hx = apply_norm(cfg.norm_kind, params["norm_x"], x, impl=cfg.norm_impl)
        cx, (xk, xv) = attn.attention(cfg, params["cross"], hx, causal=False,
                                      use_rope=False, kv_src=memory,
                                      k_chunk=k_chunk, return_kv=True)
        x = x + cx
        cache["xk"] = xk.astype(cache_dtype)
        cache["xv"] = xv.astype(cache_dtype)
    if kind == "moe":
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        y, _ = moe_mod.moe_apply(cfg, params["moe"], h2)
        x = x + y
    elif cfg.has_mlp:
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        x = x + mlp(cfg.mlp_kind, params["mlp"], h2)
    return x, cache


def block_decode(cfg: ArchConfig, kind: str, params: dict, x: jax.Array,
                 cache: dict, cache_index: jax.Array, start=None,
                 stream_kv: bool = False, layer=None) -> tuple[jax.Array, dict]:
    """One block's decode step.  With ``layer`` the ``KV_LEAVES`` of
    ``cache`` are the stacked leaves of the layer scan and the block touches
    layer ``layer`` of them alone; its other leaves are this layer's."""
    use_rope = cfg.positional == "rope"
    if kind == "mlstm":
        st = (cache["C"], cache["n"], cache["m"])
        y, (C, n, m) = xlstm_mod.mlstm_decode_step(cfg, params, x, st)
        return x + y, {"C": C, "n": n, "m": m}
    if kind == "slstm":
        st = (cache["c"], cache["n"], cache["m"], cache["h"])
        y, (c, n, m, hh) = xlstm_mod.slstm_decode_step(cfg, params, x, st)
        return x + y, {"c": c, "n": n, "m": m, "h": hh}

    window = cfg.sliding_window if kind in ("local", "hybrid") else 0
    h = apply_norm(cfg.norm_kind, params["norm1"], x, impl=cfg.norm_impl)
    kv_cache = {"k": cache["k"], "v": cache["v"]}
    a, kv_cache = attn.attention_decode_step(
        cfg, params["attn"], h, kv_cache, cache_index,
        window=window, use_rope=use_rope, start=start, stream_kv=stream_kv,
        layer=layer)
    new_cache = dict(cache)
    new_cache.update(kv_cache)
    if kind == "hybrid":
        u = jnp.einsum("bsd,de->bse", h, params["ssm_in"].astype(x.dtype))
        s_out, h_new = ssm_mod.ssm_decode_step(params["ssm"], u, cache["h_ssm"])
        s_out = jnp.einsum("bse,ed->bsd", s_out, params["ssm_out"].astype(x.dtype))
        a = 0.5 * (apply_norm("rmsnorm", params["fuse_attn_norm"], a, impl=cfg.norm_impl)
                   + apply_norm("rmsnorm", params["fuse_ssm_norm"], s_out, impl=cfg.norm_impl))
        new_cache["h_ssm"] = h_new
    x = x + a
    if "xk" in cache and "cross" in params:
        hx = apply_norm(cfg.norm_kind, params["norm_x"], x, impl=cfg.norm_impl)
        xc = {"k": cache["xk"], "v": cache["xv"]}
        enc_len = cache["xk"].shape[-2]
        cx, _ = attn.attention_decode_step(
            cfg, params["cross"], hx, xc, jnp.int32(enc_len - 1),
            use_rope=False, update_cache=False, layer=layer)
        x = x + cx
    if kind == "moe":
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        y, _ = moe_mod.moe_apply(cfg, params["moe"], h2)
        x = x + y
    elif cfg.has_mlp:
        h2 = apply_norm(cfg.norm_kind, params["norm2"], x, impl=cfg.norm_impl)
        x = x + mlp(cfg.mlp_kind, params["mlp"], h2)
    return x, new_cache


# ---------------------------------------------------------------------------
# Stack assembly
# ---------------------------------------------------------------------------

def _segments(cfg: ArchConfig, n_layers: int) -> tuple[int, tuple[str, ...]]:
    """(full_periods, tail_kinds)."""
    period = len(cfg.layer_pattern)
    full = n_layers // period
    tail = tuple(cfg.layer_pattern[i % period] for i in range(full * period, n_layers))
    return full, tail


def stack_spec(cfg: ArchConfig, n_layers: int, cross: bool = False) -> dict:
    full, tail = _segments(cfg, n_layers)
    spec: dict[str, Any] = {}
    if full:
        spec["scan"] = {
            f"p{i}": stack_tree(block_spec(cfg, kind, cross), full)
            for i, kind in enumerate(cfg.layer_pattern)
        }
    spec["tail"] = {f"t{i}": block_spec(cfg, kind, cross)
                    for i, kind in enumerate(tail)}
    return spec


def stack_cache_spec(cfg: ArchConfig, n_layers: int, batch: int, max_seq: int,
                     cache_dtype=jnp.bfloat16, cross_len: int = 0) -> dict:
    full, tail = _segments(cfg, n_layers)
    spec: dict[str, Any] = {}
    if full:
        spec["scan"] = {
            f"p{i}": stack_tree(
                block_cache_spec(cfg, kind, batch, max_seq, cache_dtype, cross_len),
                full)
            for i, kind in enumerate(cfg.layer_pattern)
        }
    spec["tail"] = {
        f"t{i}": block_cache_spec(cfg, kind, batch, max_seq, cache_dtype, cross_len)
        for i, kind in enumerate(tail)}
    return spec


def stack_forward(cfg: ArchConfig, params: dict, x: jax.Array, *,
                  causal: bool = True, memory: Optional[jax.Array] = None,
                  remat: bool = True, k_chunk: int = 1024,
                  local_block: bool = False, ring: bool = False,
                  remat_policy: str = "full") -> tuple[jax.Array, jax.Array]:
    scan_params = params.get("scan")
    aux_total = jnp.zeros((), jnp.float32)

    def period_body(x, period_params):
        aux_p = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.layer_pattern):
            if f"p{i}" not in period_params:
                continue
            x, aux = block_forward(cfg, kind, period_params[f"p{i}"], x,
                                   causal=causal, memory=memory,
                                   k_chunk=k_chunk, local_block=local_block,
                                   ring=ring)
            aux_p = aux_p + aux
        return x, aux_p

    if scan_params:
        body = period_body
        if remat:
            policy = (jax.checkpoint_policies.dots_saveable
                      if remat_policy == "dots" else None)
            body = jax.checkpoint(body, policy=policy)
        x, auxes = jax.lax.scan(lambda c, p: body(c, p), x, scan_params)
        aux_total = aux_total + auxes.sum()
    # tail layers continue the pattern: layer full*period + i has pattern
    # position i (full*period % period == 0)
    for i, (key, p) in enumerate(sorted(params.get("tail", {}).items())):
        x, aux = block_forward(cfg, _tail_kind(cfg, i), p, x, causal=causal,
                               memory=memory, k_chunk=k_chunk,
                               local_block=local_block, ring=ring)
        aux_total = aux_total + aux
    return x, aux_total


def _tail_kind(cfg: ArchConfig, tail_idx: int) -> str:
    period = len(cfg.layer_pattern)
    return cfg.layer_pattern[tail_idx % period]


def stack_prefill(cfg: ArchConfig, params: dict, x: jax.Array, *,
                  max_seq: int, cache_dtype=jnp.bfloat16,
                  memory: Optional[jax.Array] = None,
                  k_chunk: int = 1024) -> tuple[jax.Array, dict]:
    scan_params = params.get("scan")
    cache: dict[str, Any] = {"tail": {}}

    def period_body(x, period_params):
        period_cache = {}
        for i, kind in enumerate(cfg.layer_pattern):
            key = f"p{i}"
            if key not in period_params:
                continue
            x, c = block_prefill(cfg, kind, period_params[key], x,
                                 max_seq=max_seq, cache_dtype=cache_dtype,
                                 memory=memory, k_chunk=k_chunk)
            period_cache[key] = c
        return x, period_cache

    if scan_params:
        x, scanned = jax.lax.scan(jax.checkpoint(period_body), x, scan_params)
        cache["scan"] = scanned
    for i, (key, p) in enumerate(sorted(params.get("tail", {}).items())):
        x, c = block_prefill(cfg, _tail_kind(cfg, i), p, x, max_seq=max_seq,
                             cache_dtype=cache_dtype, memory=memory,
                             k_chunk=k_chunk)
        cache["tail"][key] = c
    return x, cache


def stack_decode(cfg: ArchConfig, params: dict, x: jax.Array, cache: dict,
                 cache_index: jax.Array, start=None,
                 stream_kv: bool = False) -> tuple[jax.Array, dict]:
    """Decode through the layer stack.

    The stacked cache rides in the scan CARRY (while-loop carries alias, so
    a donated cache is updated in place).  Its ``KV_LEAVES`` go to the block
    whole, with the layer index: each layer writes its one new token into
    its layer of the stack and the attention reads that layer where it
    lies, so per-step cache traffic is the read the attention needs plus one
    token written per layer.  Recurrent leaves are sliced out per layer and
    written back whole.
    """
    scan_params = params.get("scan")
    new_cache: dict[str, Any] = {"tail": {}}

    def period_body(carry, period_params):
        x, cache_st, li = carry
        cache_st = dict(cache_st)
        for i, kind in enumerate(cfg.layer_pattern):
            key = f"p{i}"
            if key not in period_params:
                continue
            stacked = cache_st[key]
            layer_cache = {
                n: c if n in KV_LEAVES
                else jax.lax.dynamic_index_in_dim(c, li, 0, keepdims=False)
                for n, c in stacked.items()}
            x, c_new = block_decode(cfg, kind, period_params[key], x,
                                    layer_cache, cache_index, start=start,
                                    stream_kv=stream_kv, layer=li)
            cache_st[key] = {
                n: c_new[n] if n in KV_LEAVES
                else jax.lax.dynamic_update_index_in_dim(
                    c, c_new[n].astype(c.dtype), li, 0)
                for n, c in stacked.items()}
        return (x, cache_st, li + 1), None

    if scan_params:
        (x, scanned_cache, _), _ = jax.lax.scan(
            period_body, (x, cache["scan"], jnp.int32(0)), scan_params)
        new_cache["scan"] = scanned_cache
    for i, (key, p) in enumerate(sorted(params.get("tail", {}).items())):
        x, c = block_decode(cfg, _tail_kind(cfg, i), p, x,
                            cache["tail"][key], cache_index, start=start,
                            stream_kv=stream_kv)
        new_cache["tail"][key] = c
    return x, new_cache
