"""kNN rating prediction — the paper's Eq. (1), mean-centered weighted average.

    r̂_uv = ū + Σ_{u'∈N_k(u), u' rated v} s_uu' · (r_u'v − ū') / Σ |s_uu'|

Neighborhoods are the k most similar users (k=13 in the paper's comparisons);
neighbors that did not rate the target item contribute nothing (their mask
zeroes both numerator and denominator terms). Batched over users with
``lax.map`` so the gathered (block, k, P) tensor stays VMEM-sized.

Two entry points per prediction shape:

- ``predict_all`` / ``predict_pairs`` take a dense (U, U) ``sims`` matrix and
  run top-k inline — the paper-table oracle path (O(U²) memory upstream).
- ``predict_all_graph`` / ``predict_pairs_graph`` take a fitted
  :class:`~repro.core.types.NeighborGraph` — the default O(U·k) path. Both
  share the same Eq. (1) epilogue: self-exclusion and <2-co-rated zeroing are
  already baked into the graph weights (weight 0 contributes nothing), and
  mean-centering is identical, so a graph built from ``sims`` by top-k
  reproduces the oracle bit-for-bit.

The graph entry points accept an optional ``n_valid`` (traced scalar): rows
``>= n_valid`` are bucket padding (``repro.lifecycle.buckets``) and their
weights are forced to 0 before Eq. (1), so a padded slot can never contribute
to a prediction or a recommendation even if its graph row holds stale data.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .types import NeighborGraph

EPS = 1e-8


def _mask_padded_rows(idx: jax.Array, w: jax.Array, n_valid,
                      shard_cap=None, tomb=None) -> jax.Array:
    """Gathered neighbor weights with padded-row ids zeroed (bucket padding).
    Operates on the (B, k) query slice — never on the full (capacity, k)
    graph — so the request-path cost stays O(B·k).

    ``n_valid=None`` (no padding) returns the weights untouched. With a
    scalar ``n_valid``, ids ``>= n_valid`` are padding (single-device
    BucketedState). With ``shard_cap`` set (static) ``n_valid`` is the (S,)
    per-shard fill of a block-partitioned ShardedLandmarkState and id
    ``s*C + slot`` is valid iff ``slot < n_valid[s]``.

    ``tomb`` is an optional (capacity,) bool of tombstoned rows (GDPR-removed
    users, ``repro.mutation``): a neighbor whose tomb bit is set contributes
    nothing to Eq. (1) even if its graph citation has not been repaired yet.
    Only the gathered (B, k) slice ``tomb[idx]`` ever exists on the request
    path — never a row-space product."""
    if tomb is not None:
        w = jnp.where(tomb[idx], 0.0, w)
    if n_valid is None:
        return w
    if shard_cap is None:
        return jnp.where(idx < n_valid, w, 0.0)
    return jnp.where(idx % shard_cap < n_valid[idx // shard_cap], w, 0.0)


def _topk_neighbors(sim_row: jax.Array, self_idx: jax.Array, k: int):
    """Top-k neighbor (indices, weights), excluding the user itself."""
    row = sim_row.at[self_idx].set(-jnp.inf)
    vals, idx = jax.lax.top_k(row, k)
    vals = jnp.where(jnp.isfinite(vals), vals, 0.0)
    return idx, vals


def _center(ratings: jax.Array):
    """(mask, per-user means, mean-centered ratings) for Eq. (1)."""
    mask = (ratings != 0).astype(ratings.dtype)
    cnt = mask.sum(axis=1)
    means = jnp.where(cnt > 0, ratings.sum(axis=1) / jnp.maximum(cnt, 1.0), 0.0)
    return mask, means, (ratings - means[:, None]) * mask


def _block_predict(idx, w, centered, mask, mu):
    """Eq. (1) for one user block given its (block, k) neighbor lists."""
    nb_centered = centered[idx]  # gathers: (block, k, P)
    nb_mask = mask[idx]
    # HIGHEST: at DEFAULT precision the TPU feeds f32 operands to the MXU
    # as bf16, an error of ~1e-2 in a rating prediction
    hi = jax.lax.Precision.HIGHEST
    num = jnp.einsum("bk,bkp->bp", w, nb_centered, precision=hi)
    den = jnp.einsum("bk,bkp->bp", jnp.abs(w), nb_mask, precision=hi)
    return mu[:, None] + num / jnp.maximum(den, EPS)


@partial(jax.jit, static_argnames=("k", "block"))
def predict_all(
    sims: jax.Array,  # (U, U) user-user similarity
    ratings: jax.Array,  # (U, P), 0 == missing
    k: int = 13,
    block: int = 256,
) -> jax.Array:
    """Predict the full (U, P) matrix with the kNN rule. Returns r̂ for all cells."""
    n_users = ratings.shape[0]
    mask, means, centered = _center(ratings)

    n_blocks = -(-n_users // block)
    pad = n_blocks * block - n_users
    sims_p = jnp.pad(sims, ((0, pad), (0, 0)))
    means_p = jnp.pad(means, (0, pad))
    user_ids = jnp.arange(n_blocks * block)

    def one_block(b):
        rows = jax.lax.dynamic_slice_in_dim(sims_p, b * block, block, axis=0)
        ids = jax.lax.dynamic_slice_in_dim(user_ids, b * block, block)
        idx, w = jax.vmap(_topk_neighbors, in_axes=(0, 0, None))(rows, ids, k)
        mu = jax.lax.dynamic_slice_in_dim(means_p, b * block, block)
        return _block_predict(idx, w, centered, mask, mu)

    preds = jax.lax.map(one_block, jnp.arange(n_blocks))
    preds = preds.reshape(n_blocks * block, -1)[:n_users]
    return preds


@partial(jax.jit, static_argnames=("block",))
def predict_all_graph(
    graph: NeighborGraph,  # (U, k) fitted neighbor lists
    ratings: jax.Array,  # (U, P), 0 == missing
    block: int = 256,
) -> jax.Array:
    """``predict_all`` from a NeighborGraph — no (U, U) array anywhere."""
    n_users = ratings.shape[0]
    mask, means, centered = _center(ratings)

    n_blocks = -(-n_users // block)
    pad = n_blocks * block - n_users
    idx_p = jnp.pad(graph.indices, ((0, pad), (0, 0)))
    w_p = jnp.pad(graph.weights, ((0, pad), (0, 0)))
    means_p = jnp.pad(means, (0, pad))

    def one_block(b):
        idx = jax.lax.dynamic_slice_in_dim(idx_p, b * block, block, axis=0)
        w = jax.lax.dynamic_slice_in_dim(w_p, b * block, block, axis=0)
        mu = jax.lax.dynamic_slice_in_dim(means_p, b * block, block)
        return _block_predict(idx, w, centered, mask, mu)

    preds = jax.lax.map(one_block, jnp.arange(n_blocks))
    preds = preds.reshape(n_blocks * block, -1)[:n_users]
    return preds


def _pair_predict(idx, w, u, v, ratings, mask, means):
    r = ratings[idx, v]
    m = mask[idx, v]
    num = jnp.sum(w * (r - means[idx]) * m)
    den = jnp.sum(jnp.abs(w) * m)
    return means[u] + num / jnp.maximum(den, EPS)


@partial(jax.jit, static_argnames=("k",))
def predict_pairs(
    sims: jax.Array,
    ratings: jax.Array,
    users: jax.Array,  # (B,) query user ids
    items: jax.Array,  # (B,) query item ids
    k: int = 13,
) -> jax.Array:
    """Predict only the requested (user, item) pairs — the test-fold path."""
    mask, means, _ = _center(ratings)

    def one(u, v):
        idx, w = _topk_neighbors(sims[u], u, k)
        return _pair_predict(idx, w, u, v, ratings, mask, means)

    return jax.vmap(one)(users, items)


@partial(jax.jit, static_argnames=("n", "shard_cap"))
def recommend_topn_graph(
    graph: NeighborGraph,
    ratings: jax.Array,  # (U, P), 0 == missing
    users: jax.Array,  # (B,) query user ids
    n: int = 10,
    *,
    n_valid=None,  # () int32 (or (S,) with shard_cap): bucket-padding mask
    shard_cap=None,  # static per-shard capacity of a sharded graph
    tomb=None,  # (capacity,) bool: tombstoned rows never contribute
):
    """Top-N unseen items per query user — the serve-path recommendation op.

    Scores every item with Eq. (1) from the user's fitted neighbor list, masks
    items the user already rated, and returns ``(items, scores)`` of shape
    (B, n). Cold rows (all weights 0) fall back to the user mean, so ranking
    degrades to arbitrary-but-finite rather than NaN. A user with fewer than
    ``n`` unrated items gets id -1 / score -inf in the exhausted slots — a
    rated item is never returned. ``n_valid`` zeroes padded-row neighbor
    weights (see module docstring).
    """
    mask, means, centered = _center(ratings)
    idx = graph.indices[users]  # (B, k)
    w = _mask_padded_rows(idx, graph.weights[users], n_valid,
                          shard_cap, tomb).astype(centered.dtype)
    preds = _block_predict(idx, w, centered, mask, means[users])  # (B, P)
    preds = jnp.where(mask[users] > 0, -jnp.inf, preds)  # never re-recommend
    scores, items = jax.lax.top_k(preds, n)
    items = jnp.where(jnp.isfinite(scores), items, -1)
    return items, scores


@partial(jax.jit, static_argnames=("shard_cap",))
def predict_pairs_graph(
    graph: NeighborGraph,
    ratings: jax.Array,
    users: jax.Array,  # (B,) query user ids
    items: jax.Array,  # (B,) query item ids
    *,
    n_valid=None,  # () int32 (or (S,) with shard_cap): bucket-padding mask
    shard_cap=None,  # static per-shard capacity of a sharded graph
    tomb=None,  # (capacity,) bool: tombstoned rows never contribute
) -> jax.Array:
    """``predict_pairs`` from a NeighborGraph — no (U, U) array anywhere.

    ``n_valid`` zeroes padded-row neighbor weights (see module docstring).
    """
    mask, means, _ = _center(ratings)
    idx_b = graph.indices[users]  # (B, k)
    w_b = _mask_padded_rows(idx_b, graph.weights[users], n_valid, shard_cap,
                            tomb)

    def one(idx, w, u, v):
        return _pair_predict(idx, w, u, v, ratings, mask, means)

    return jax.vmap(one)(idx_b, w_b, users, items)
