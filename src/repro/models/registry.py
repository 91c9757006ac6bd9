"""Model API: param/cache/input specs + forward/decode for every arch.

``Model`` is a thin, stateless facade over the functional blocks — the same
object drives smoke tests (reduced configs, real arrays), the trainer, the
server, and the dry-run (ShapeDtypeStructs only).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.dist.sharding import constrain
from repro.models import transformer as tfm
from repro.models.layers import apply_norm, embed, embedding_spec, norm_spec, unembed
from repro.models.module import ParamSpec


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # -- parameter declaration -------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg
        spec: dict[str, Any] = {
            "embed": embedding_spec(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_spec(cfg.norm_kind, cfg.d_model),
            "stack": tfm.stack_spec(cfg, cfg.n_layers, cross=cfg.encdec),
        }
        if not cfg.tie_embeddings:
            spec["unembed"] = embedding_spec(cfg.vocab_size, cfg.d_model)
        if cfg.positional == "learned":
            spec["pos_embed"] = {
                "table": ParamSpec((cfg.max_position, cfg.d_model), jnp.float32,
                                   (None, "embed"), init="embed", init_scale=0.02)}
        if cfg.encdec:
            spec["encoder"] = {
                "stack": tfm.stack_spec(cfg, cfg.n_encoder_layers, cross=False),
                "final_norm": norm_spec(cfg.norm_kind, cfg.d_model),
                "pos_embed": {
                    "table": ParamSpec((cfg.n_frontend_tokens, cfg.d_model),
                                       jnp.float32, (None, "embed"),
                                       init="embed", init_scale=0.02)},
            }
        if cfg.param_dtype != "float32":
            dt = jnp.dtype(cfg.param_dtype)
            spec = jax.tree.map(
                lambda s: dataclasses.replace(s, dtype=dt),
                spec, is_leaf=lambda s: isinstance(s, ParamSpec))
        return spec

    # -- inputs ------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        cfg = self.cfg
        b = shape.global_batch
        if shape.is_decode:
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            }
        else:
            s_tok = shape.seq_len - (cfg.n_frontend_tokens
                                     if cfg.frontend == "patch" else 0)
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, s_tok), jnp.int32),
                "labels": jax.ShapeDtypeStruct((b, s_tok), jnp.int32),
            }
            if cfg.frontend == "patch":
                specs["patches"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_frontend_tokens, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
        if cfg.frontend == "frame":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        return specs

    def cache_specs(self, batch: int, max_seq: int,
                    cache_dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        cross_len = cfg.n_frontend_tokens if cfg.encdec else 0
        return tfm.stack_cache_spec(cfg, cfg.n_layers, batch, max_seq,
                                    cache_dtype, cross_len)

    # -- encoder (whisper) --------------------------------------------------
    def encode(self, params: dict, frames: jax.Array, *,
               remat: bool = True, k_chunk: int = 1024) -> jax.Array:
        cfg = self.cfg
        enc = params["encoder"]
        t = frames.shape[1]
        x = frames + enc["pos_embed"]["table"][:t].astype(frames.dtype)
        x, _ = tfm.stack_forward(cfg, enc["stack"], x, causal=False,
                                 remat=remat, k_chunk=k_chunk)
        return apply_norm(cfg.norm_kind, enc["final_norm"], x, impl=cfg.norm_impl)

    # -- full-sequence forward (train / prefill) ----------------------------
    def forward(self, params: dict, batch: dict, *, remat: bool = True,
                k_chunk: int = 1024, local_block: bool = False,
                ring: bool = False, remat_policy: str = "full",
                return_hidden: bool = False) -> tuple[jax.Array, jax.Array]:
        """Returns (logits [B,S,V], aux_loss) — or the final hidden states
        [B,S,d] with ``return_hidden`` (the trainer then computes a chunked
        cross-entropy that never materialises full-sequence logits)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = embed(params["embed"], batch["tokens"], dtype)
        if cfg.frontend == "patch" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(dtype), x], axis=1)
            x = constrain(x, "batch", "seq", "embed")
        if cfg.positional == "learned":
            s = x.shape[1]
            x = x + params["pos_embed"]["table"][:s].astype(dtype)
        memory = None
        if cfg.encdec:
            memory = self.encode(params, batch["frames"].astype(dtype),
                                 remat=remat, k_chunk=k_chunk)
        x, aux = tfm.stack_forward(cfg, params["stack"], x, causal=True,
                                   memory=memory, remat=remat, k_chunk=k_chunk,
                                   local_block=local_block, ring=ring,
                                   remat_policy=remat_policy)
        x = apply_norm(cfg.norm_kind, params["final_norm"], x, impl=cfg.norm_impl)
        if return_hidden:
            return x, aux
        logits = unembed(params.get("unembed", params["embed"]), x)
        return logits, aux

    def unembed_table(self, params: dict) -> jax.Array:
        return params.get("unembed", params["embed"])["table"]

    # -- prefill: forward + populate decode cache ----------------------------
    def prefill(self, params: dict, batch: dict, max_seq: int, *,
                cache_dtype=jnp.bfloat16, k_chunk: int = 1024
                ) -> tuple[jax.Array, dict]:
        """Returns (logits [B,S,V], cache filled for positions [0, S))."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = embed(params["embed"], batch["tokens"], dtype)
        if cfg.frontend == "patch" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(dtype), x], axis=1)
        if cfg.positional == "learned":
            x = x + params["pos_embed"]["table"][:x.shape[1]].astype(dtype)
        memory = None
        if cfg.encdec:
            memory = self.encode(params, batch["frames"].astype(dtype),
                                 k_chunk=k_chunk)
        from repro.models import transformer as _tfm
        x, cache = _tfm.stack_prefill(cfg, params["stack"], x,
                                      max_seq=max_seq, cache_dtype=cache_dtype,
                                      memory=memory, k_chunk=k_chunk)
        x = apply_norm(cfg.norm_kind, params["final_norm"], x, impl=cfg.norm_impl)
        logits = unembed(params.get("unembed", params["embed"]), x)
        return logits, cache

    def prefill_into(self, params: dict, cache: dict, tokens: jax.Array,
                     slot, row, length) -> tuple[jax.Array, dict]:
        """Prefill one request into slot ``slot`` of a batched decode cache.

        ``tokens`` [1, Pb] is the prompt right-padded to Pb, of which the
        first ``length`` are real.  Its post-rope K/V, at request-local
        positions 0..Pb-1, go to rows [row, row + Pb) of the slot in every
        layer: what ``decode_step`` with ``start = row`` would have written
        token by token.  Rows from ``row + length`` on hold the padding's
        K/V, which the slot's own decode steps overwrite before reading.
        Only the hidden state at ``length - 1`` is unembedded.  Returns
        (logits [1, V], cache)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = embed(params["embed"], tokens, dtype)
        x, new = tfm.stack_prefill(cfg, params["stack"], x,
                                   max_seq=tokens.shape[1],
                                   cache_dtype=jax.tree.leaves(cache)[0].dtype)
        cache = tfm.stack_write_rows(cache, new, slot, row)
        x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        x = apply_norm(cfg.norm_kind, params["final_norm"], x, impl=cfg.norm_impl)
        logits = unembed(params.get("unembed", params["embed"]), x)
        return logits[:, 0], cache

    # -- single-token decode -------------------------------------------------
    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    cache_index: jax.Array, start=None,
                    stream_kv: bool = False) -> tuple[jax.Array, dict]:
        """tokens: [B,1] -> (logits [B,1,V], new cache).  ``start`` [B]
        gives each slot's admission index (continuous batching);
        ``stream_kv`` reads sequence-sharded KV caches through the decode
        ring (``serve_rules(long_context=True)``)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = embed(params["embed"], tokens, dtype)
        if cfg.positional == "learned":
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos_embed"]["table"], cache_index, 1, axis=0
            ).astype(dtype)[None]
        x, new_cache = tfm.stack_decode(cfg, params["stack"], x, cache,
                                        cache_index, start=start,
                                        stream_kv=stream_kv)
        x = apply_norm(cfg.norm_kind, params["final_norm"], x, impl=cfg.norm_impl)
        logits = unembed(params.get("unembed", params["embed"]), x)
        return logits, new_cache

    # -- convenience ---------------------------------------------------------
    def init_params(self, rng: jax.Array) -> dict:
        from repro.models import module
        return module.init(rng, self.param_specs())

    def init_cache(self, batch: int, max_seq: int, cache_dtype=jnp.bfloat16) -> dict:
        from repro.models import module
        return module.init(jax.random.PRNGKey(0),
                           self.cache_specs(batch, max_seq, cache_dtype))


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
