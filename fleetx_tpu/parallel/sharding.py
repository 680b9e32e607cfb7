"""Logical-axis sharding rules — the GSPMD expression of hybrid parallelism.

The reference wires tensor parallelism through explicit Megatron layers
(``ColumnParallelLinear``/``RowParallelLinear``/``VocabParallelEmbedding``,
consumed at ``hybrid_model.py:111-112,590``) and ZeRO through
``group_sharded_parallel`` (``eager_engine.py:228-242``).  Here both are pure
metadata: model code annotates parameters/activations with *logical* axis
names, and one rule table maps logical names to mesh axes.  GSPMD then inserts
exactly the collectives the reference hand-wires (all-reduce after row-parallel
matmul, all-gather for sequence parallelism, reduce-scatter for ZeRO grads).

Since the partition-rule registry landed (``parallel/rules.py``), the rule
table itself is DATA owned by :class:`~fleetx_tpu.parallel.rules.SpecLayout`
— this module keeps the runtime faces: ``make_axis_rules`` (the historical
name every call site and test uses), the flax-context helpers, and the
ZeRO-1/2/3 placement helpers, whose per-leaf policy is the registry's
:func:`~fleetx_tpu.parallel.rules.with_fsdp_axis` so the runtime and the
static shardcheck auditor cannot disagree on where a ZeRO axis lands.

Logical axis vocabulary: ``rules.LOGICAL_AXES``.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import flax.linen as nn

from fleetx_tpu.parallel.rules import SpecLayout, with_fsdp_axis

__all__ = ["make_axis_rules", "logical_sharding", "zero_sharding",
           "zero_grad_specs", "shard_logical"]


def make_axis_rules(dist_config: dict | None = None) -> tuple[tuple[str, Any], ...]:
    """Build logical→mesh axis rules from a ``Distributed`` config section.

    Thin wrapper over the registry's canonical table
    (``rules.SpecLayout.axis_rules`` — tensor parallelism via
    ``vocab/mlp/heads → tensor``, ``embed → fsdp`` at ZeRO stage 3,
    Megatron-SP's ``act_seq → (seq, tensor)``, ring attention's
    ``act_seq → seq``); kept as the historical call-site name.
    """
    return SpecLayout.from_dist_config(dist_config).axis_rules()


def logical_sharding(abstract_tree: Any, mesh: Mesh,
                     rules: tuple[tuple[str, Any], ...]) -> Any:
    """Map a tree of logically-annotated abstract arrays to NamedShardings."""
    specs = nn.get_partition_spec(abstract_tree)
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, nn.logical_to_mesh_axes(spec, rules)),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_logical(x: jax.Array, logical_axes: tuple[str | None, ...],
                  rules: tuple[tuple[str, Any], ...]) -> jax.Array:
    """Constrain an activation to its logical sharding inside jit."""
    spec = nn.logical_to_mesh_axes(P(*logical_axes), rules)
    return jax.lax.with_sharding_constraint(x, spec)


def _fsdp_leaf_fn(mesh: Mesh, axis: str):
    """The ONE ZeRO per-leaf placement closure shared by
    ``zero_sharding`` (optimizer state, stage 1/2) and ``zero_grad_specs``
    (gradients, stage 2) — policy lives in ``rules.with_fsdp_axis``."""
    size = mesh.shape[axis]

    def leaf_spec(leaf: Any, existing: Any = None) -> Any:
        shape = tuple(getattr(leaf, "shape", ()))
        spec = tuple(getattr(existing, "spec", P())) if existing is not None \
            else ()
        return NamedSharding(mesh, P(*with_fsdp_axis(shape, spec, size,
                                                     axis=axis)))

    return leaf_spec


def zero_sharding(tree: Any, mesh: Mesh, axis: str = "fsdp",
                  param_shardings: Any = None) -> Any:
    """ZeRO-1/2 optimizer-state sharding over the ``fsdp`` axis.

    The reference's sharding stage 1/2 (``group_sharded_parallel`` with
    ``level="os_g"``, ``eager_engine.py:228-242``) shards optimizer state while
    keeping params replicated.  Here: each optimizer-state leaf keeps its
    param's spec (tensor parallel / stage 3) and additionally shards the
    first still-replicated dimension divisible by the fsdp axis size — the
    placement ``zero_grad_specs`` gives the gradients; leaves with no such
    dimension (scalars, small vectors) keep the param spec.
    """
    leaf_spec = _fsdp_leaf_fn(mesh, axis)
    if param_shardings is not None:
        return jax.tree.map(leaf_spec, tree, param_shardings)
    return jax.tree.map(leaf_spec, tree)


def zero_grad_specs(tree: Any, mesh: Mesh, axis: str = "fsdp",
                    param_shardings: Any = None) -> Any:
    """ZeRO-2 *gradient* sharding over the ``fsdp`` axis (docs/zero_sharding.md).

    Stage 2 of the reference's ``group_sharded_parallel`` (``level="os_g"``)
    shards gradients as well as optimizer state.  Constraining the grad
    pytree (and the grad-accumulation scan carry) to these shardings inside
    the jitted step lets GSPMD lower the data-parallel grad sync to
    reduce-scatter + sharded update + param allgather instead of a full
    allreduce followed by a replicated update — the scheme of "Automatic
    Cross-Replica Sharding of Weight Update in Data-Parallel Training"
    (PAPERS.md).

    Same per-leaf placement as ``zero_sharding``.  Leaves with no free
    divisible dimension (scalars, tiny vectors) keep the param spec — GSPMD
    falls back to the plain allreduce for those few bytes.  Specs are
    canonical (no trailing ``None``).
    """
    return zero_sharding(tree, mesh, axis, param_shardings)
