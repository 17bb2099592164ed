"""shard_map query router — owner-routed request path for the sharded state.

``buckets.predict_pairs_sharded`` / ``recommend_topn_sharded`` are plain
GSPMD calls: ``graph.indices[users]`` and ``ratings[idx]`` gather across the
row-sharded arrays and XLA is free to (and on host meshes does) satisfy them
by all-gathering operands — a request-path collective proportional to the
*population*, not the batch. This module replaces them with an explicit
two-phase ``shard_map`` route in which only query-sized tensors ever cross
shards:

  phase 1  each query's **owner** shard (``user // C``) contributes its
           (k,) graph row, its mean, and (top-N only) its (P,) rated mask;
           one psum of the one-hot-masked contributions reassembles the
           replicated (b, k) neighbor lists.
  phase 2  each *neighbor's* owner shard contributes that neighbor's rating
           at the query item (pairs) or its centered rating row (top-N);
           a second psum reassembles (b, k) / (b, k, P).
  epilogue Eq. (1) replayed on the routed operands — the *same* expression
           tree as ``core.knn``, so the reduction shapes and order match the
           single-device path exactly.

Bit-identity argument: every psum sums exactly one real contribution with
S-1 zeros (``x + 0.0 == x`` for every float x; a ``-0.0`` weight can flip to
``+0.0``, which ``==``-compares and predicts identically), the per-row stats
(mask/mean/centered) are computed shard-locally from identical row data, and
the epilogue reductions have identical shape and operand order — so routed
results match ``core.knn`` under ``np.array_equal``, the same bar the
sharded shadow-replica waves assert. Collective payload per request:
O(b·k) for pairs, O(b·k·P) for top-N — never O(U).

:func:`materialization_check` is the router's jaxpr proof (the request-path
sibling of the fold-in no-replication check): no eqn in the traced route
materializes a full (S·C, ·) row-space array outside a pass-through, and no
per-query (b, ≥S·C) dense-score tensor exists anywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import obs as obslib
from repro.core import knn
from repro.distributed.sharding import shard_linear_index


def _count_routed_launch(family: str, rows: int) -> None:
    """Per-launch accounting for the routed entry points — one counter
    bump when an Observability is installed, a single global read + None
    check otherwise. Shapes are concrete even under tracing, so the
    counters also tick (once) per trace/compile."""
    o = obslib.current()
    if o is not None and o.enabled:
        obslib.count_launch(o.registry, f"router.{family}", rows)


def _local_row_stats(ratings_l: jax.Array):
    """Per-row (mask, means) of this shard's (C, P) block — literally
    ``knn._center`` restricted to local rows; per-row reductions make the
    local values bitwise equal to the global ones."""
    mask = (ratings_l != 0).astype(ratings_l.dtype)
    cnt = mask.sum(axis=1)
    means = jnp.where(cnt > 0,
                      ratings_l.sum(axis=1) / jnp.maximum(cnt, 1.0), 0.0)
    return mask, means


def predict_pairs_routed(sstate, users: jax.Array, items: jax.Array,
                         tomb=None) -> jax.Array:
    """Routed pair predictions: Eq. (1) with neighbor data owner-routed.

    ``users`` are sharded row ids (``shard * capacity + slot``), same as
    ``buckets.predict_pairs_sharded`` — and the results match it (and the
    single-device ``knn.predict_pairs_graph``) under ``np.array_equal``.
    ``tomb`` is the write path's replicated (S·C,) tombstone bitmap
    (``mutation.MutableStateSharded``): tombstoned neighbors contribute
    nothing, in the same mask order as ``knn._mask_padded_rows`` (tomb
    zeroing first, then the padded-slot mask) so the routed result stays
    bit-identical to the single-device mutable read path.
    """
    _count_routed_launch("pair", int(users.shape[0]))
    return _predict_pairs_routed(sstate, users, items, tomb)


@jax.jit
def _predict_pairs_routed(sstate, users: jax.Array, items: jax.Array,
                          tomb=None) -> jax.Array:
    mesh, axes = sstate.mesh, sstate.axes
    cap = sstate.capacity
    graph = sstate.state.graph
    row2 = P(axes, None)
    opt_tomb = [tomb] if tomb is not None else []

    def inner(gi_l, gw_l, ratings_l, nv, users, items, tomb_r):
        lin = shard_linear_index(mesh, axes)
        tomb_r = tomb_r[0] if tomb_r else None
        mask_l, means_l = _local_row_stats(ratings_l)
        # phase 1: query owners contribute graph row + mean
        own_q = (users // cap) == lin
        slot_q = users % cap
        idx = jax.lax.psum(
            jnp.where(own_q[:, None], gi_l[slot_q], 0), axes)
        w = jax.lax.psum(
            jnp.where(own_q[:, None], gw_l[slot_q], 0.0), axes)
        mu_q = jax.lax.psum(jnp.where(own_q, means_l[slot_q], 0.0), axes)
        # tombstone + padded-slot masking — same order as _mask_padded_rows
        if tomb_r is not None:
            w = jnp.where(tomb_r[idx], 0.0, w)
        w = jnp.where(idx % cap < nv[idx // cap], w, 0.0)
        # phase 2: neighbor owners contribute rating-at-item + mean
        own_n = (idx // cap) == lin  # (b, k)
        slot_n = idx % cap
        r = jax.lax.psum(
            jnp.where(own_n, ratings_l[slot_n, items[:, None]], 0.0), axes)
        mu_n = jax.lax.psum(jnp.where(own_n, means_l[slot_n], 0.0), axes)
        # Eq. (1) epilogue — identical expression tree to knn._pair_predict
        # (vmap of a (k,) sum lowers to the same axis-1 reduction)
        m = (r != 0).astype(ratings_l.dtype)
        num = jnp.sum(w * (r - mu_n) * m, axis=1)
        den = jnp.sum(jnp.abs(w) * m, axis=1)
        return mu_q + num / jnp.maximum(den, knn.EPS)

    return shard_map(
        inner, mesh=mesh,
        in_specs=(row2, row2, row2, P(None), P(None), P(None),
                  [P(None)] * len(opt_tomb)),
        out_specs=P(None),
        check_vma=False,
    )(graph.indices, graph.weights, sstate.state.ratings, sstate.n_valid,
      users.astype(jnp.int32), items.astype(jnp.int32), opt_tomb)


def recommend_topn_routed(sstate, users: jax.Array, n: int = 10, tomb=None):
    """Routed top-N: neighbor *rows* are owner-routed as (b, k, P) centered
    contributions, then the exact ``knn._block_predict`` einsum epilogue +
    rated-item mask + ``lax.top_k`` replay on the routed operands.

    Matches ``buckets.recommend_topn_sharded`` (items and scores) under
    ``np.array_equal``. ``tomb`` masks tombstoned neighbors exactly like
    :func:`predict_pairs_routed`.
    """
    _count_routed_launch("topn", int(users.shape[0]))
    return _recommend_topn_routed(sstate, users, n, tomb)


@partial(jax.jit, static_argnames=("n",))
def _recommend_topn_routed(sstate, users: jax.Array, n: int, tomb=None):
    mesh, axes = sstate.mesh, sstate.axes
    cap = sstate.capacity
    graph = sstate.state.graph
    row2 = P(axes, None)
    opt_tomb = [tomb] if tomb is not None else []

    def inner(gi_l, gw_l, ratings_l, nv, users, tomb_r):
        lin = shard_linear_index(mesh, axes)
        tomb_r = tomb_r[0] if tomb_r else None
        mask_l, means_l = _local_row_stats(ratings_l)
        dt = ratings_l.dtype
        centered_l = (ratings_l - means_l[:, None]) * mask_l
        # phase 1: owner contributes graph row, mean, and rated mask
        own_q = (users // cap) == lin
        slot_q = users % cap
        idx = jax.lax.psum(
            jnp.where(own_q[:, None], gi_l[slot_q], 0), axes)
        w = jax.lax.psum(
            jnp.where(own_q[:, None], gw_l[slot_q], 0.0), axes)
        mu_q = jax.lax.psum(jnp.where(own_q, means_l[slot_q], 0.0), axes)
        rated = jax.lax.psum(
            jnp.where(own_q[:, None], mask_l[slot_q], 0.0), axes)  # (b, P)
        if tomb_r is not None:
            w = jnp.where(tomb_r[idx], 0.0, w)
        w = jnp.where(idx % cap < nv[idx // cap], w, 0.0).astype(dt)
        # phase 2: neighbor owners contribute centered rows + masks
        own_n = (idx // cap) == lin  # (b, k)
        slot_n = idx % cap
        nb_c = jax.lax.psum(
            jnp.where(own_n[:, :, None], centered_l[slot_n], 0.0), axes)
        nb_m = jax.lax.psum(
            jnp.where(own_n[:, :, None], mask_l[slot_n], 0.0), axes)
        # knn._block_predict epilogue, then the never-re-recommend mask
        hi = jax.lax.Precision.HIGHEST  # as knn._block_predict
        num = jnp.einsum("bk,bkp->bp", w, nb_c, precision=hi)
        den = jnp.einsum("bk,bkp->bp", jnp.abs(w), nb_m, precision=hi)
        preds = mu_q[:, None] + num / jnp.maximum(den, knn.EPS)
        preds = jnp.where(rated > 0, -jnp.inf, preds)
        scores, items = jax.lax.top_k(preds, n)
        items = jnp.where(jnp.isfinite(scores), items, -1)
        return items, scores

    return shard_map(
        inner, mesh=mesh,
        in_specs=(row2, row2, row2, P(None), P(None),
                  [P(None)] * len(opt_tomb)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(graph.indices, graph.weights, sstate.state.ratings, sstate.n_valid,
      users.astype(jnp.int32), opt_tomb)


# compile-count accounting (serve compile-budget assert, exec.* gauges)
# reads `_cache_size` off the public entry points — forward it through the
# launch-counting wrappers to the underlying jitted callables
predict_pairs_routed._cache_size = _predict_pairs_routed._cache_size
recommend_topn_routed._cache_size = _recommend_topn_routed._cache_size


def materialization_check(sstate, b: int, n: int = 10):
    """Jaxpr proof for the routed request path: trace both routed entry
    points at batch ``b`` and assert no eqn output (i) carries the full
    ``S*C`` row dimension outside a shard_map/jit pass-through — a
    replicated row-space materialization — or (ii) is a per-query
    ``(b, >= S*C)`` tensor anywhere, including inside shard_map bodies —
    the dense (b, U) score matrix a gather-based scorer would build.
    Returns ``(n_avals_scanned, offenders)``.
    """
    rows = sstate.state.ratings.shape[0]
    p = sstate.state.ratings.shape[1]
    k = sstate.state.graph.k
    if rows <= max(b, p, k * sstate.shard_count):
        raise ValueError(
            f"materialization check is vacuous at S*C={rows} rows "
            f"(b={b}, P={p}, S*k={k * sstate.shard_count}); "
            "serve a larger population")
    users = jnp.zeros((b,), jnp.int32)
    items = jnp.zeros((b,), jnp.int32)
    traced = [
        jax.make_jaxpr(lambda s, u, i: predict_pairs_routed(s, u, i))(
            sstate, users, items),
        jax.make_jaxpr(lambda s, u: _recommend_topn_routed(s, u, n))(
            sstate, users),
    ]
    seen, bad = [], []

    def scan(jx, inside):
        for eqn in jx.eqns:
            is_sh = eqn.primitive.name == "shard_map"
            passthrough = is_sh or eqn.primitive.name == "jit"
            for v in eqn.outvars:
                shp = getattr(v.aval, "shape", None) or ()
                seen.append(shp)
                if shp and shp[0] >= rows and (inside or not passthrough):
                    bad.append((eqn.primitive.name, shp))
                if len(shp) >= 2 and shp[0] == b and shp[1] >= rows:
                    bad.append((eqn.primitive.name, shp))
            for pv in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        pv, is_leaf=lambda x: hasattr(x, "jaxpr")
                        or hasattr(x, "eqns")):
                    ij = getattr(sub, "jaxpr", sub)
                    if hasattr(ij, "eqns"):
                        scan(ij, inside or is_sh)

    for jx in traced:
        scan(jx.jaxpr, False)
    return len(seen), bad
