"""Row-sharded embedding lookup — the recsys model-parallel hot path.

JAX has no ``nn.EmbeddingBag`` and no CSR sparse; the system implements it as
``jnp.take`` + mask + segment/sum reduction, with the table row-sharded over
the 'model' mesh axis via ``shard_map``: each shard gathers the ids that fall
in its row range locally and the partial embeddings are ``psum``-ed over
'model' (payload = (B, D) activations, never the table).

Without a mesh (CPU smoke tests) the plain ``jnp.take`` path runs.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _local_lookup(table_shard: jax.Array, ids: jax.Array, axis: str) -> jax.Array:
    """Inside shard_map: mask ids outside this shard's row range, take, psum."""
    shard_size = table_shard.shape[0]
    lo = jax.lax.axis_index(axis) * shard_size
    local = ids - lo
    ok = (local >= 0) & (local < shard_size) & (ids >= 0)
    emb = jnp.take(table_shard, jnp.clip(local, 0, shard_size - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    return jax.lax.psum(emb, axis)


def embedding_lookup(
    table: jax.Array,  # (V, D)
    ids: jax.Array,  # (...,) int32, -1 == padding
    mesh: Optional[Mesh] = None,
    batch_axes: Tuple[str, ...] = ("pod", "data"),
    row_axis: str = "model",
) -> jax.Array:
    """Gather rows; padding ids (-1) return zeros. Output shape ids.shape + (D,)."""
    if mesh is None or row_axis not in mesh.axis_names:
        ok = ids >= 0
        emb = jnp.take(table, jnp.maximum(ids, 0), axis=0)
        return jnp.where(ok[..., None], emb, 0.0)
    if table.shape[0] % mesh.shape[row_axis] != 0:
        raise ValueError(
            f"table rows {table.shape[0]} must divide the '{row_axis}' axis "
            f"({mesh.shape[row_axis]}); pad the table (configs use round_up(·, 512))."
        )

    baxes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_b = 1
    for a in baxes:
        n_b *= mesh.shape[a]
    if not baxes or ids.shape[0] % n_b != 0:  # batch-1 / ragged: replicate ids
        baxes = ()
    id_spec = P(baxes if baxes else None, *([None] * (ids.ndim - 1)))
    out_spec = P(baxes if baxes else None, *([None] * ids.ndim))
    fn = shard_map(
        partial(_local_lookup, axis=row_axis),
        mesh=mesh,
        in_specs=(P(row_axis, None), id_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(table, ids)


def embedding_bag(
    table: jax.Array,
    ids: jax.Array,  # (B, F) multi-hot bag, -1 padding
    mode: str = "sum",
    weights: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """EmbeddingBag(sum|mean) over the bag dim — torch parity via take+reduce."""
    emb = embedding_lookup(table, ids, mesh)  # (B, F, D)
    m = (ids >= 0).astype(emb.dtype)[..., None]
    if weights is not None:
        m = m * weights[..., None]
    s = (emb * m).sum(axis=-2)
    if mode == "sum":
        return s
    return s / jnp.maximum(m.sum(axis=-2), 1.0)


def distributed_topk(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k over the last (possibly sharded) dim. Under GSPMD the all-gather
    payload is the score vector (4 MB at 1M candidates), so plain lax.top_k is
    already the two-stage pattern after XLA partitions it."""
    return jax.lax.top_k(scores, k)
