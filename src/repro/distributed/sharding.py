"""Logical-axis sharding rules (MaxText-style, hand-rolled).

Every parameter/activation declares a tuple of *logical* axis names; a rules
dict maps logical names → mesh axes. Swapping rules is how the perf hillclimb
changes sharding without touching model code.

Mesh axes: ('pod', 'data', 'model') multi-pod or ('data', 'model') single-pod.

Logical axes:
  fsdp      weight dim fully sharded over the data(+pod) axes (ZeRO-3)
  tp        tensor-parallel dim (heads / d_ff / vocab / experts)
  expert    MoE expert dim (maps to 'model' — EP shares the TP axis)
  batch     activation batch dim
  kv_seq    decode KV-cache sequence dim (flash-decoding split-K)
  edge      GNN edge-array dim (sharded over every axis, flattened)
  rows      embedding-table row dim (recsys model parallelism)
  layers / null   stacked-scan layer dim / replicated
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

LogicalAxes = Tuple[Optional[str], ...]

# Default rules; configs may override per-arch (e.g. smollm replicates heads).
DEFAULT_RULES: Dict[str, Any] = {
    "fsdp": ("pod", "data"),
    "tp": "model",
    "expert": "model",
    "batch": ("pod", "data"),
    "seq": "model",  # sequence-parallel residual (Megatron SP): gather at block entry,
    #                  reduce-scatter at exit; shrinks scan-saved activations 16x.
    "kv_seq": "model",
    "kv_seq_all": ("data", "model"),  # long-context batch=1: shard seq everywhere
    "edge": ("pod", "data", "model"),
    "rows": "model",
    "layers": None,
    "null": None,
    "vocab": "model",
}


def filter_rules(rules: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Drop mesh axes that don't exist (single-pod mesh has no 'pod')."""
    names = set(mesh.axis_names)

    def fix(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        kept = tuple(a for a in v if a in names)
        return kept if kept else None

    return {k: fix(v) for k, v in rules.items()}


def spec_for(logical: LogicalAxes, rules: Dict[str, Any]) -> P:
    return P(*(rules.get(ax) if ax is not None else None for ax in logical))


def sharding_for(logical: LogicalAxes, mesh: Mesh, rules: Dict[str, Any]) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical, filter_rules(rules, mesh)))


def tree_shardings(logical_tree, mesh: Mesh, rules: Dict[str, Any]):
    """Map a pytree of logical-axes tuples to a pytree of NamedShardings."""
    rules = filter_rules(rules, mesh)
    return jax.tree_util.tree_map(
        lambda la: NamedSharding(mesh, spec_for(la, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x: jax.Array, logical: LogicalAxes, rules: Dict[str, Any], mesh=None) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside a mesh context
    (``jax.set_mesh``)."""
    mesh = mesh or jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, sharding_for(logical, mesh, rules))


def divisible(dim: int, axes, mesh: Mesh) -> bool:
    """Can ``dim`` be sharded over ``axes`` of ``mesh``?"""
    if axes is None:
        return True
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return dim % n == 0


# --------------------------------------------------------------------------
# CF row-block sharding (ShardedLandmarkState, core/landmark_cf.py).
#
# The serving artifact block-partitions user rows over the mesh row axes with
# the same linearization as ``streaming_knn_graph_sharded``: shard s (the
# mesh-linearized index over ``axes``) owns rows [s*C, (s+1)*C) of every
# row-indexed array, where C is the per-shard bucket capacity
# (lifecycle/buckets.py schedules). A *sharded row id* is ``s * C + slot``;
# a fitted state's contiguous *dense* ids map through ``dense_to_sharded_ids``
# (shard = id // u_per, slot = id % u_per with u_per = ceil(U / S)).
# --------------------------------------------------------------------------


def cf_row_axes(mesh: Mesh, row_axes=("pod", "data")) -> Tuple[str, ...]:
    """The subset of ``row_axes`` that exists on ``mesh`` (mesh-order kept)."""
    return tuple(a for a in row_axes if a in mesh.axis_names)


def cf_shard_count(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def cf_row_sharding(mesh: Mesh, axes, ndim: int = 2) -> NamedSharding:
    """Rows block-partitioned over ``axes``, trailing dims replicated."""
    return NamedSharding(mesh, P(axes, *(None,) * (ndim - 1)))


def shard_linear_index(mesh: Mesh, axes) -> jax.Array:
    """Inside shard_map: this shard's linearized index over ``axes`` —
    identical to the linearization of streaming_knn_graph_sharded."""
    lin = jax.numpy.int32(0)
    for a in axes:
        lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
    return lin


def dense_to_sharded_ids(ids, u_per: int, capacity: int):
    """Map contiguous fitted row ids to the block-partitioned id space."""
    return (ids // u_per) * capacity + ids % u_per


def remap_block_ids(ids, old_capacity: int, new_capacity: int):
    """Re-express sharded row ids after a per-shard capacity regrow."""
    return (ids // old_capacity) * new_capacity + ids % old_capacity


def pack_row_blocks(x: "np.ndarray", n_shards: int, u_per: int,
                    capacity: int) -> "np.ndarray":
    """(U, ...) dense rows -> (S*C, ...) zero-padded per-shard blocks
    (host-side; callers device_put with :func:`cf_row_sharding`)."""
    import numpy as np

    x = np.asarray(x)
    u = x.shape[0]
    out = np.zeros((n_shards * capacity,) + x.shape[1:], x.dtype)
    for s in range(n_shards):
        lo, hi = s * u_per, min((s + 1) * u_per, u)
        if hi > lo:
            out[s * capacity:s * capacity + (hi - lo)] = x[lo:hi]
    return out


def repack_row_blocks(x: "np.ndarray", n_shards: int, old_capacity: int,
                      new_capacity: int) -> "np.ndarray":
    """Grow every per-shard block from C_old to C_new rows (host-side)."""
    import numpy as np

    x = np.asarray(x)
    assert new_capacity >= old_capacity, (old_capacity, new_capacity)
    blocks = x.reshape((n_shards, old_capacity) + x.shape[1:])
    pad = [(0, 0)] * blocks.ndim
    pad[1] = (0, new_capacity - old_capacity)
    return np.pad(blocks, pad).reshape((n_shards * new_capacity,) + x.shape[1:])


def repack_row_blocks_device(x: jax.Array, n_shards: int, old_capacity: int,
                             new_capacity: int, mesh: Mesh, axes) -> jax.Array:
    """Device-side :func:`repack_row_blocks` — no host round-trip.

    The (S*C_old, ...) -> (S, C_old, ...) reshape, the zero-pad of the slot
    axis and the reshape back are all block-local under the row sharding
    (S divides the leading dim the same way the sharding does), so the regrow
    compiles to a per-device pad; the trailing ``device_put`` re-asserts the
    canonical row sharding without moving payload across hosts.
    """
    assert new_capacity >= old_capacity, (old_capacity, new_capacity)
    blocks = x.reshape((n_shards, old_capacity) + x.shape[1:])
    pad = [(0, 0)] * blocks.ndim
    pad[1] = (0, new_capacity - old_capacity)
    out = jax.numpy.pad(blocks, pad).reshape(
        (n_shards * new_capacity,) + x.shape[1:])
    return jax.device_put(out, cf_row_sharding(mesh, axes, ndim=x.ndim))


def shard_local_append(x: jax.Array, rows: jax.Array, n_valid: jax.Array,
                       target: jax.Array, mesh: Mesh, axes) -> jax.Array:
    """Write ``rows`` into shard ``target`` at its fill offset — the
    shard-local append of the sharded fold-in. ``x`` is (S*C, ...) row-sharded,
    ``rows`` (b, ...) replicated, ``n_valid`` the (S,) per-shard fill counts,
    ``target`` a traced scalar. Non-target shards are untouched; no cross-shard
    traffic beyond the already-replicated ``rows``."""
    from jax import shard_map

    nd = x.ndim

    def inner(x_l, rows, n_valid, target):
        lin = shard_linear_index(mesh, axes)
        upd = jax.lax.dynamic_update_slice(
            x_l, rows.astype(x_l.dtype),
            (n_valid[target],) + (0,) * (nd - 1))
        return jax.numpy.where(lin == target, upd, x_l)

    row_spec = P(axes, *(None,) * (nd - 1))
    return shard_map(
        inner, mesh=mesh,
        in_specs=(row_spec, P(*(None,) * nd), P(None), P()),
        out_specs=row_spec, check_vma=False,
    )(x, rows, n_valid, target)


def shard_batch_full(x: jax.Array, mesh: Optional[Mesh], axis: int = 0) -> jax.Array:
    """Constrain dim ``axis`` of x over EVERY mesh axis (recsys batches are
    huge and the models tiny — compute scales with all chips, and the
    embedding shard_map reshards ids internally as needed)."""
    if mesh is None or mesh.empty:
        return x
    axes = tuple(mesh.axis_names)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if x.shape[axis] % n != 0:
        return x
    spec = [None] * x.ndim
    spec[axis] = axes
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
