"""Logical-axis sharding: rules, the active-mesh context, and ``constrain``.

Models annotate every parameter and activation with *logical* axis names
("batch", "seq", "embed", "heads", "expert", ...).  A :class:`ShardingRules`
maps each logical axis to zero or more *physical* mesh axes; the mapping is
applied lazily so the same model code runs unchanged on a single CPU device,
a 4-device host mesh, or a multi-pod production mesh.

Resolution (``ShardingRules.spec``) enforces two invariants the property
tests pin down:

  * **dedup** — a physical mesh axis is used by at most one dimension of a
    tensor (first logical axis wins);
  * **divisibility** — a physical axis is only assigned when the dimension
    size is divisible by the mesh axis size (partial assignment of a tuple
    rule keeps the divisible prefix).

``use_mesh(mesh, rules)`` activates a mesh for the enclosing trace;
``constrain(x, *logical_axes)`` then lowers to
``jax.lax.with_sharding_constraint``.  Outside any active mesh — or under
``use_mesh(None, None)`` — ``constrain`` is an exact no-op, which is what
lets single-device tests exercise the fully-annotated model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Mapping, Optional, Sequence, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import compat

# A rule value: no sharding, one mesh axis, or an ordered tuple of mesh axes.
Physical = Union[None, str, tuple]


def _axis_sizes(mesh) -> dict:
    """{axis_name: size} for anything mesh-shaped (incl. test fakes)."""
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical->physical axis mapping.

    Derive variants with ``ShardingRules({**rules.rules, "seq": "model"})``.
    """

    rules: Mapping[str, Physical]

    def physical(self, logical: Optional[str]) -> tuple:
        """Candidate physical axes for one logical axis (may be empty)."""
        if logical is None:
            return ()
        phys = self.rules.get(logical)
        if phys is None:
            return ()
        return (phys,) if isinstance(phys, str) else tuple(phys)

    def spec(self, logical_axes: Sequence[Optional[str]], *,
             shape: Optional[Sequence[int]] = None, mesh=None) -> P:
        """PartitionSpec for a tensor with the given logical axes.

        ``shape`` enables the divisibility check; ``mesh`` enables the
        membership check (rules may name axes the mesh does not have) and
        supplies axis sizes.  Both invariants from the module docstring are
        enforced here.
        """
        sizes = _axis_sizes(mesh) if mesh is not None else {}
        used: set = set()
        entries: list = []
        for i, name in enumerate(logical_axes):
            dim = None if shape is None else shape[i]
            kept: list = []
            prod = 1
            for ax in self.physical(name):
                if mesh is not None and ax not in sizes:
                    continue
                if ax in used:
                    continue
                n = sizes.get(ax, 1)
                if dim is not None and dim % (prod * n):
                    continue
                kept.append(ax)
                used.add(ax)
                prod *= n
            if not kept:
                entries.append(None)
            elif len(kept) == 1:
                entries.append(kept[0])
            else:
                entries.append(tuple(kept))
        return P(*entries)


def train_rules(fsdp: bool = False, seq_parallel: bool = False) -> ShardingRules:
    """Training layout: batch over (pod, data), tensor parallel over model.

    ``fsdp`` additionally shards the weight "embed" dimension over the data
    axis (ZeRO-3 style); activations keep their batch->data assignment, so
    dedup leaves activation embed dims replicated.  ``seq_parallel`` shards
    the activation sequence axis over the model axis (pairs with ring
    attention).
    """
    return ShardingRules({
        "batch": ("pod", "data"),
        "seq": "model" if seq_parallel else None,
        "embed": "data" if fsdp else None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "expert_mlp": "model",
        "layers": None,
        "cache_seq": None,
        "heads_act": None,
        "kv_heads_act": None,
    })


def serve_rules(long_context: bool = False) -> ShardingRules:
    """Decode layout: weights tensor-parallel, activations replicated per
    TP rank ("heads_act"/"kv_heads_act" -> None).

    ``long_context`` switches the KV cache from head sharding to sequence
    sharding ("cache_seq" -> model, "kv_heads" -> None): the attend_decode
    softmax over the sharded axis becomes a distributed log-sum-exp, so the
    multi-GB cache never moves.  The cache leaf names its heads before its
    sequence, and the first logical axis takes the mesh axis, so the heads
    must give it up; the K/V projections are then replicated, and each
    device makes the new token that its sequence shard may hold.
    """
    return ShardingRules({
        "batch": ("pod", "data"),
        "seq": None,
        "embed": None,
        "heads": "model",
        "kv_heads": None if long_context else "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "expert_mlp": "model",
        "layers": None,
        "cache_seq": "model" if long_context else None,
        "heads_act": None,
        "kv_heads_act": None,
    })


# --------------------------------------------------------------------------
# Active-mesh context
# --------------------------------------------------------------------------

_STATE = threading.local()


def _stack() -> list:
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    return stack


@contextlib.contextmanager
def use_mesh(mesh, rules: Optional[ShardingRules] = None):
    """Activate ``(mesh, rules)`` for the enclosing trace.

    ``use_mesh(None, None)`` pushes an explicit "no mesh" frame — inside it
    ``constrain`` is a no-op even when an outer frame holds a real mesh.
    """
    _stack().append((mesh, rules))
    try:
        yield mesh
    finally:
        _stack().pop()


def active_mesh():
    stack = _stack()
    return stack[-1][0] if stack else None


def active_rules() -> Optional[ShardingRules]:
    stack = _stack()
    return stack[-1][1] if stack else None


def per_shard(fn, x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """``fn`` applied to each device's shard of ``x`` under the active mesh,
    ``x`` laid out by ``logical_axes`` as ``constrain`` lays it out; plain
    ``fn(x)`` without a mesh.  For ops the SPMD partitioner cannot split,
    such as a layout constraint: it would gather ``x`` whole for them."""
    mesh = active_mesh()
    rules = active_rules()
    if mesh is None or rules is None:
        return fn(x)
    spec = rules.spec(logical_axes, shape=x.shape, mesh=mesh)
    return compat.shard_map(fn, mesh, in_specs=(spec,), out_specs=spec)(x)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Sharding-constrain ``x`` under the active mesh; no-op without one."""
    mesh = active_mesh()
    rules = active_rules()
    if mesh is None or rules is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"constrain: {len(logical_axes)} logical axes for "
                         f"rank-{x.ndim} tensor {x.shape}")
    spec = rules.spec(logical_axes, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Tree / batch shardings (dry-run entry points)
# --------------------------------------------------------------------------

def tree_shardings(tree: Any, mesh, rules: ShardingRules) -> Any:
    """NamedSharding tree for a ParamSpec tree (params, opt state, caches)."""
    from repro.models import module

    def one(spec):
        axes = spec.logical_axes or (None,) * len(spec.shape)
        return NamedSharding(mesh, rules.spec(axes, shape=spec.shape, mesh=mesh))

    return module.tree_map_specs(one, tree)


# Logical axes of the model-input tensors, by input name.
_BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "patches": ("batch", "seq", "embed"),
    "frames": ("batch", "seq", "embed"),
}


def batch_shardings(batch_specs: Mapping[str, jax.ShapeDtypeStruct], mesh,
                    rules: ShardingRules) -> dict:
    """NamedShardings for a model-input dict of ShapeDtypeStructs."""
    out = {}
    for key, sds in batch_specs.items():
        axes = _BATCH_AXES.get(key, ("batch",) + (None,) * (len(sds.shape) - 1))
        axes = tuple(axes[:len(sds.shape)])
        out[key] = NamedSharding(mesh, rules.spec(axes, shape=sds.shape,
                                                  mesh=mesh))
    return out
