"""Distributed execution layer: logical-axis sharding and ring attention.

Two submodules:

  * :mod:`repro.dist.sharding` — :class:`ShardingRules` (logical->physical
    axis mapping with dedup + divisibility resolution), the
    ``use_mesh``/``active_mesh`` context, and ``constrain``.
  * :mod:`repro.dist.ring_attention` — blockwise ring attention with
    ``ppermute`` rotation and an online-softmax accumulator.

The ``constrain`` no-op contract
--------------------------------

``constrain(x, *logical_axes)`` applies
``jax.lax.with_sharding_constraint`` **only** while a ``use_mesh(mesh,
rules)`` context is active for the current thread's trace; with no active
mesh — or inside an explicit ``use_mesh(None, None)`` frame — it returns
``x`` unchanged, with no tracing or device-placement side effects.  Model
code is therefore annotated unconditionally: the same functions run on a
bare CPU device in unit tests (constraints vanish) and on a production mesh
in the dry-run/launcher (constraints lower to SPMD resharding).  Axis names
unknown to the active rules, axes missing from the mesh, and non-divisible
dimension sizes all resolve to "replicated" rather than erroring, so rule
sets can be written for the production mesh and still work on small test
meshes.

:mod:`repro.dist.compat` holds the one spelling of mesh construction and
shard_map (Auto axis types, no replication check); all mesh construction
and shard_map entry in ``repro`` goes through it.
"""
from repro.dist.sharding import (ShardingRules, active_mesh, active_rules,
                                 batch_shardings, constrain, serve_rules,
                                 train_rules, tree_shardings, use_mesh)
from repro.dist.ring_attention import ring_attention

__all__ = [
    "ShardingRules", "active_mesh", "active_rules", "batch_shardings",
    "constrain", "ring_attention", "serve_rules", "train_rules",
    "tree_shardings", "use_mesh",
]
