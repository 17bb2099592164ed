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

A row's mask, mean and centred values depend on that row alone, so the
request-path entry points take them from the rows a batch gathers — its
users' and their neighbours' — and never pass over the whole matrix:
``recommend_topn_graph`` always, ``predict_pairs_graph`` whenever those
``B·(k+1)`` rows are no more than the matrix holds (:func:`gathers_row_stats`,
a static shape rule; a larger batch, such as the lifecycle monitor's holdout
reservoir, reads less in one pass). Both paths apply :func:`_center` to the
same f32 rows.
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
    """(mask, per-row means, mean-centered ratings) for Eq. (1) of any block
    of rating rows (``(..., P)``): the whole matrix or gathered rows. Each
    row's statistics depend on that row alone."""
    mask = (ratings != 0).astype(ratings.dtype)
    cnt = mask.sum(axis=-1)
    means = jnp.where(cnt > 0, ratings.sum(axis=-1) / jnp.maximum(cnt, 1.0),
                      0.0)
    return mask, means, (ratings - means[..., None]) * mask


def gathers_row_stats(rows: int, k: int, capacity: int) -> bool:
    """Whether a pair batch of ``rows`` users takes Eq. (1)'s row means
    from its ``rows·(k+1)`` gathered rows (True) or from one pass over the
    ``capacity`` rows of the matrix (False): whichever reads fewer rows."""
    return rows * (k + 1) <= capacity


def _block_predict(w, nb_centered, nb_mask, mu):
    """Eq. (1) for one user block given its gathered (block, k, P) neighbour
    rows, centred and masked."""
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
        return _block_predict(w, centered[idx], mask[idx], mu)

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
        return _block_predict(w, centered[idx], mask[idx], mu)

    preds = jax.lax.map(one_block, jnp.arange(n_blocks))
    preds = preds.reshape(n_blocks * block, -1)[:n_users]
    return preds


def _pair_predict(w, r, mu_nb, mu_u):
    """Eq. (1) for one pair from its k neighbours' ratings ``r`` of the
    item, their means ``mu_nb`` and the user's mean ``mu_u``."""
    m = (r != 0).astype(r.dtype)
    num = jnp.sum(w * (r - mu_nb) * m)
    den = jnp.sum(jnp.abs(w) * m)
    return mu_u + num / jnp.maximum(den, EPS)


@partial(jax.jit, static_argnames=("k",))
def predict_pairs(
    sims: jax.Array,
    ratings: jax.Array,
    users: jax.Array,  # (B,) query user ids
    items: jax.Array,  # (B,) query item ids
    k: int = 13,
) -> jax.Array:
    """Predict only the requested (user, item) pairs — the test-fold path."""
    _, means, _ = _center(ratings)

    def one(u, v):
        idx, w = _topk_neighbors(sims[u], u, k)
        return _pair_predict(w, ratings[idx, v], means[idx], means[u])

    return jax.vmap(one)(users, items)


@partial(jax.jit, static_argnames=("n", "shard_cap", "n_items"))
def recommend_topn_graph(
    graph: NeighborGraph,
    ratings: jax.Array,  # (U, P), 0 == missing
    users: jax.Array,  # (B,) query user ids
    n: int = 10,
    *,
    n_valid=None,  # () int32 (or (S,) with shard_cap): bucket-padding mask
    shard_cap=None,  # static per-shard capacity of a sharded graph
    tomb=None,  # (capacity,) bool: tombstoned rows never contribute
    n_items=None,  # static: columns >= n_items are padding, never items
):
    """Top-N unseen items per query user — the serve-path recommendation op.

    Scores every item with Eq. (1) from the user's fitted neighbor list, masks
    items the user already rated, and returns ``(items, scores)`` of shape
    (B, n). Cold rows (all weights 0) fall back to the user mean, so ranking
    degrades to arbitrary-but-finite rather than NaN. A user with fewer than
    ``n`` unrated items gets id -1 / score -inf in the exhausted slots — a
    rated item is never returned. ``n_valid`` zeroes padded-row neighbor
    weights (see module docstring). ``n_items`` cuts the zero columns of a
    lane-aligned matrix (``repro.lifecycle.buckets``) off the scored items.
    The row statistics come from the gathered rows (module docstring).
    """
    idx = graph.indices[users]  # (B, k)
    w = _mask_padded_rows(idx, graph.weights[users], n_valid,
                          shard_cap, tomb).astype(ratings.dtype)
    nb_mask, _, nb_centered = _center(ratings[idx])  # (B, k, P)
    u_mask, mu, _ = _center(ratings[users])  # (B, P)
    preds = _block_predict(w, nb_centered, nb_mask, mu)[:, :n_items]
    rated = u_mask[:, :n_items] > 0
    preds = jnp.where(rated, -jnp.inf, preds)  # never re-recommend
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
    idx_b = graph.indices[users]  # (B, k)
    w_b = _mask_padded_rows(idx_b, graph.weights[users], n_valid, shard_cap,
                            tomb)
    if gathers_row_stats(users.shape[0], graph.k, ratings.shape[0]):
        _, mu_nb, _ = _center(ratings[idx_b])  # means of (B, k, P) rows
        _, mu_u, _ = _center(ratings[users])
    else:
        _, means, _ = _center(ratings)
        mu_nb, mu_u = means[idx_b], means[users]
    r_b = ratings[idx_b, items[:, None]]  # (B, k): each neighbour's rating
    return jax.vmap(_pair_predict)(w_b, r_b, mu_nb, mu_u)
