"""Logical-axis sharding rules → PartitionSpecs.

The GSPMD replacement for the reference's wrapper-based strategies
(torch DDP/FSDP in train/torch/train_loop_utils.py): models annotate each
parameter/activation dimension with a *logical* axis name; a ShardingRules
table maps logical names to mesh axes. Swapping DP↔FSDP↔TP↔SP is a rules
change — the model code never changes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """Maps logical dimension names to mesh axes (None = replicated).

    ``sequence`` is S wherever a whole model's activations carry it (the
    batch, q / k / v, the loss): ``"sp"`` under context parallelism, whose
    attention works on slices of S. ``stream`` is S of the residual stream
    alone, between blocks, for a model whose block takes slices of S in and
    gives slices back (``models/exchange.py: exchanged_over_tp``): over tp,
    the axis the block's weights are split over, so that the block's sum
    over tp and the gather that undoes it become exchanges of slices. A
    model states the stream under it only where
    ``exchange.tp_exchange_mesh`` finds the mesh and the shape fit (tp above
    1 and dividing S, ``sequence`` over no axis); everywhere else the
    stream's S is ``sequence``."""

    batch: MeshAxes = ("dp", "fsdp")
    sequence: MeshAxes = None  # set to "sp" for context parallelism
    stream: MeshAxes = "tp"  # S of the residual stream between blocks
    embed: MeshAxes = "fsdp"  # weight-sharding axis (ZeRO-3 analog)
    heads: MeshAxes = "tp"
    kv_heads: MeshAxes = "tp"
    head_dim: MeshAxes = None
    mlp: MeshAxes = "tp"
    vocab: MeshAxes = "tp"
    expert: MeshAxes = "ep"
    layers: MeshAxes = None  # leading axis of scan-stacked params

    def spec(self, *logical_axes: Optional[str]) -> PartitionSpec:
        parts = []
        for name in logical_axes:
            if name is None:
                parts.append(None)
            else:
                parts.append(getattr(self, name))
        return PartitionSpec(*parts)


# Rules presets ---------------------------------------------------------

def dp_rules() -> ShardingRules:
    """Pure data parallelism: replicate weights, shard batch."""
    return ShardingRules(embed=None, heads=None, kv_heads=None, mlp=None,
                         vocab=None)


def fsdp_rules() -> ShardingRules:
    """Fully-sharded DP (ZeRO-3): weights sharded over fsdp, no TP."""
    return ShardingRules(heads=None, kv_heads=None, mlp=None, vocab=None)


def tp_fsdp_rules() -> ShardingRules:
    """2D: Megatron TP on heads/mlp/vocab + FSDP on the embed dim."""
    return ShardingRules()


def context_parallel_rules() -> ShardingRules:
    """TP+FSDP+sequence sharding (ring attention over sp)."""
    return ShardingRules(sequence="sp")


# Shard-slice math (checkpoint resharding) ------------------------------
# Pure-index GSPMD block partitioning: given a parameter's global shape,
# a PartitionSpec-like spec, and a mesh described as ordered
# (axis, size) pairs, compute which index block one mesh coordinate
# owns. Balanced ``array_split`` boundaries (first ``S % N`` shards get
# one extra row) so a checkpoint saved on 8 ranks can be resharded onto
# 6 — elastic shrink/grow never requires divisibility.


def axis_split_bounds(dim_size: int, num_shards: int):
    """[(start, stop)] per shard along one dimension, balanced."""
    if num_shards <= 0:
        raise ValueError(f"num_shards must be positive, got {num_shards}")
    base, extra = divmod(dim_size, num_shards)
    bounds = []
    start = 0
    for i in range(num_shards):
        stop = start + base + (1 if i < extra else 0)
        bounds.append((start, stop))
        start = stop
    return bounds


def _spec_dim_axes(dim_spec) -> Tuple[str, ...]:
    """Normalize one dimension's spec entry to a tuple of mesh axes."""
    if dim_spec is None:
        return ()
    if isinstance(dim_spec, str):
        return (dim_spec,)
    return tuple(dim_spec)


def shard_slices(global_shape, spec, axes, coords) -> Tuple[slice, ...]:
    """The index block one mesh position owns under ``spec``.

    ``axes`` maps mesh axis name -> size; ``coords`` maps axis name ->
    this position's index on that axis. A dimension sharded over a
    tuple of axes composes them row-major (same ordering GSPMD uses).
    Dimensions with no spec entry (or None) are fully replicated.
    """
    out = []
    for d, size in enumerate(global_shape):
        dim_axes = _spec_dim_axes(spec[d]) if d < len(spec) else ()
        n = 1
        idx = 0
        for name in dim_axes:
            n *= int(axes[name])
            idx = idx * int(axes[name]) + int(coords[name])
        if n <= 1:
            out.append(slice(0, size))
        else:
            start, stop = axis_split_bounds(size, n)[idx]
            out.append(slice(start, stop))
    return tuple(out)


def slices_overlap(a, b):
    """Intersection of two same-rank slice tuples, or None if empty."""
    out = []
    for sa, sb in zip(a, b):
        start = max(sa.start, sb.start)
        stop = min(sa.stop, sb.stop)
        if start >= stop:
            return None
        out.append(slice(start, stop))
    return tuple(out)


# Activations -----------------------------------------------------------
# Parameters get their sharding from the rules when the state is built. An
# activation gets one only where the model states it: left alone, the
# partitioner derives it from the weights (``embed -> fsdp`` splits the
# hidden states' d), and reshards at every operation that wants the batch
# split instead. So a model states it at the lookup, at the layer scan's
# exit and in the loss, through these two.

def ambient_spec(mesh, *logical_axes: Optional[str]) -> PartitionSpec:
    """The spec of logical axes under the rules registered beside the
    current mesh (``mesh.set_current_mesh``; the default table if none
    were), with the mesh axes ``mesh`` lacks left out. A mesh axis that two
    dimensions name stays with the later one: ("batch", "embed") is batch
    over dp and d over fsdp, as a lookup in an fsdp-split table leaves it."""
    from ray_tpu.parallel.mesh import current_rules
    spec = (current_rules() or ShardingRules()).spec(*logical_axes)
    free, dims = set(mesh.axis_names), []
    for dim in reversed(spec):
        dims.append(tuple(a for a in _spec_dim_axes(dim) if a in free))
        free -= set(dims[-1])
    return PartitionSpec(*(dim or None for dim in reversed(dims)))


def constrain(x, *logical_axes: Optional[str]):
    """``x`` with its sharding stated in logical axes, under the mesh of
    the step being traced. Nothing without a mesh, or on one device."""
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, ambient_spec(mesh, *logical_axes)))


# Helpers ---------------------------------------------------------------

def named_sharding(mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_shardings(mesh, spec_tree):
    """Map a pytree of PartitionSpecs to NamedShardings on `mesh`."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def shard_tree(tree, mesh, spec_tree):
    """Device_put a pytree with the given specs (zero-copy when possible)."""
    shardings = tree_shardings(mesh, spec_tree)
    return jax.device_put(tree, shardings)
