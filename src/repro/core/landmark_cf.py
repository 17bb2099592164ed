"""LandmarkCF — the paper's Algorithm 3 as a composable JAX module.

Pipeline (user-based; item-based transposes the rating matrix first):

  1. ``select_landmarks``            — one of the five strategies (§3.3)
  2. ``d1 = masked_similarity``      — (U, n) user-landmark representation
  3. ``graph.build_neighbor_graph``  — (U, k) top-k NeighborGraph in landmark
                                       space (d2); the (U, U) matrix never
                                       touches HBM on this default path
  4. ``knn.predict_*_graph``         — Eq. (1) rating prediction

Complexity: O(|U|·n·|P|) compute + O(|U|·(n+k)) fit memory instead of
O(|U|²·|P|) / O(|U|²). ``fit(..., dense_sims=True)`` is the escape hatch that
keeps the dense (U, U) d2 matrix for paper-table parity and oracle tests.

``fit_distributed`` is the pod-scale variant (DESIGN.md §3): users sharded over
the ('pod','data') mesh axes, landmarks replicated. The only cross-shard
payload is the (U, n) landmark representation — a |P|/n reduction in collective
bytes versus sharded full-matrix CF — and the graph build all-gathers one
candidate chunk at a time (streaming_knn_graph_sharded).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import knn
from .graph import build_neighbor_graph, extend_neighbor_graph, finalize_topk
from .selection import select_landmarks
from .similarity import (
    dense_similarity,
    full_similarity_matrix,
    masked_similarity,
    streaming_knn_graph_sharded,
)
from .types import LandmarkSpec, NeighborGraph, RatingMatrix


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LandmarkState:
    """Fitted state: landmark ids, reduced representation, neighbor graph.

    Exactly one of ``graph`` (default O(U·k) artifact) and ``sims`` (the dense
    (U, U) escape hatch: ``fit(..., dense_sims=True)`` / ``fit_baseline``) is
    set; prediction dispatches on which one is present.
    """

    landmark_idx: jax.Array  # (n,)
    representation: jax.Array  # (U, n) users in landmark space
    ratings: jax.Array  # (U, P) the (possibly transposed) training block
    graph: Optional[NeighborGraph] = None  # (U, k) neighbor ids + weights
    sims: Optional[jax.Array] = None  # (U, U) dense escape hatch

    def tree_flatten(self):
        return (self.landmark_idx, self.representation, self.ratings,
                self.graph, self.sims), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedLandmarkState:
    """A serving ``LandmarkState`` block-partitioned over mesh row axes.

    Every row-indexed array of ``state`` has leading dimension ``S * C``
    (S = mesh shards over ``axes``, C = per-shard bucket capacity from
    ``lifecycle.buckets``) and is placed with ``PartitionSpec(axes, None)`` —
    shard s (mesh-linearized, the ``streaming_knn_graph_sharded``
    linearization) owns rows ``[s*C, (s+1)*C)``. Graph neighbor ids and
    ``landmark_idx`` live in this *sharded* id space (``s*C + slot``);
    ``n_valid[s]`` counts the live rows of shard s, the rest is zero filler.

    ``row_rank[s*C + slot]`` is the row's *logical* id — its position in the
    single-device arrival order (fit rows 0..U-1, then fold-in batches in
    stream order). Within a shard, slots are always appended in logical
    order, so local top-k tie-breaking is canonical for free; the cross-shard
    merge of fold-in candidate lists breaks exact-weight ties by this rank,
    which makes the sharded graph's neighbor lists — and therefore every
    prediction — **bit-identical** to the single-device run even when d1
    collisions produce duplicate weights (they do, frequently).

    ``mesh``/``axes`` ride in the pytree aux data, so jitted steps treat them
    as static and the whole state passes through jit/shard_map as arrays only.
    """

    state: LandmarkState
    n_valid: jax.Array  # (S,) int32 live rows per shard block
    row_rank: jax.Array  # (S*C,) int32 logical id per slot (tie canonicalizer)
    mesh: jax.sharding.Mesh
    axes: tuple

    def tree_flatten(self):
        return (self.state, self.n_valid, self.row_rank), (self.mesh, self.axes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], *aux)

    @property
    def shard_count(self) -> int:
        from repro.distributed.sharding import cf_shard_count

        return cf_shard_count(self.mesh, self.axes)

    @property
    def capacity(self) -> int:
        """Per-shard row capacity C."""
        return self.state.ratings.shape[0] // self.shard_count

    @property
    def total_valid(self) -> int:
        import numpy as np

        return int(np.asarray(self.n_valid).sum())


def _oriented(ratings: jax.Array, mode: str) -> jax.Array:
    if mode == "user":
        return ratings
    if mode == "item":
        return ratings.T
    raise ValueError(f"mode must be user|item, got {mode!r}")


def build_representation(
    ratings: jax.Array, landmark_idx: jax.Array, d1: str, sim_fn=None
) -> jax.Array:
    """d1 step: (U, n) similarities/distances of every user to the landmarks.

    ``sim_fn`` lets callers swap in the fused Pallas kernel (ops.masked_similarity).
    """
    fn = sim_fn if sim_fn is not None else masked_similarity
    return fn(ratings, ratings[landmark_idx], d1)


def fit(
    key: jax.Array,
    matrix: RatingMatrix,
    spec: LandmarkSpec,
    sim_fn=None,
    *,
    dense_sims: bool = False,
    backend: Optional[str] = None,
) -> LandmarkState:
    """Fit landmark CF on a single host/device (the paper-scale path).

    Default: the fitted artifact is a (U, k) NeighborGraph built by
    ``core.graph`` (backend from ``spec.graph_backend`` unless overridden) —
    the (U, U) d2 matrix is never materialized. ``dense_sims=True`` keeps the
    dense matrix instead (paper-table parity / oracle comparisons).
    """
    r = _oriented(matrix.ratings, spec.mode)
    idx = select_landmarks(key, r, spec.n_landmarks, spec.selection)
    rep = build_representation(r, idx, spec.d1, sim_fn)
    if dense_sims:
        sims = dense_similarity(rep, rep, spec.d2)
        return LandmarkState(idx, rep, r, sims=sims)
    graph = build_neighbor_graph(rep, spec.d2, spec.k_neighbors,
                                 backend=backend or spec.graph_backend)
    return LandmarkState(idx, rep, r, graph=graph)


@partial(jax.jit, static_argnames=("spec", "sim_fn", "backend", "chunk",
                                   "ivf"))
def fold_in(
    state: LandmarkState,
    new_ratings: jax.Array,  # (b, P) new rows of the *oriented* matrix
    spec: LandmarkSpec,
    sim_fn=None,
    *,
    backend: Optional[str] = None,
    chunk: int = 4096,
    ivf=None,  # retrieval.IVFSpec (static) for backend="ivf"
    ivf_index=None,  # live retrieval.IVFIndex over the existing rows
) -> LandmarkState:
    """Project b new users into the fitted state without a refit — the serve
    path (Lu & Shen 1505.07900: the new-user similarity-list update).

    d1 is O(b·n·P) against the frozen landmark rows; the graph grows via
    :func:`~repro.core.graph.extend_neighbor_graph` (new-vs-all candidate scan
    + back-patch of existing rows), so no (U, U) or (U+b, U+b) array ever
    exists. Landmarks, d1/d2 measures and k are frozen at fit time — matching
    a from-scratch ``fit`` on the concatenated matrix with the *same*
    landmarks to within top-k tie-breaking (oracle test in tests/test_graph).

    ``backend="ivf"`` (or ``spec.graph_backend == "ivf"``) makes the
    new-vs-all half sublinear through an IVF index over the landmark space;
    pass the serve loop's ``ivf_index`` so the O(U) index build is not paid
    per fold-in (docs/retrieval.md — note the returned state does NOT carry
    the index; append the batch to the caller's index separately).

    ``new_ratings`` rows follow the state's orientation (new users in user
    mode, new items in item mode). The whole update jits: ``LandmarkState`` in,
    ``LandmarkState`` out, all pure pytree ops.
    """
    if state.graph is None:
        raise ValueError(
            "fold_in needs a graph-backed state; dense-sims states "
            "(fit(..., dense_sims=True) / fit_baseline) must refit")
    landmarks = state.ratings[state.landmark_idx]  # (n, P) frozen at fit
    fn = sim_fn if sim_fn is not None else masked_similarity
    new_rep = fn(new_ratings, landmarks, spec.d1)  # (b, n)
    graph = extend_neighbor_graph(
        state.graph, state.representation, new_rep, spec.d2,
        backend=backend or spec.graph_backend, chunk=chunk,
        ivf=ivf, ivf_index=ivf_index)
    return LandmarkState(
        state.landmark_idx,
        jnp.concatenate([state.representation, new_rep]),
        jnp.concatenate([state.ratings, new_ratings]),
        graph=graph,
    )


@partial(jax.jit, static_argnames=("spec",))
def fold_in_sharded(
    sstate: ShardedLandmarkState,
    new_ratings: jax.Array,  # (bq, P) batch bucket; rows >= b_valid are filler
    b_valid: jax.Array,  # () int32 real rows in the batch
    target_shard: jax.Array,  # () int32 shard that receives the batch
    spec: LandmarkSpec,
    landmarks: jax.Array = None,  # (n, P) frozen basis override (mutation path)
) -> ShardedLandmarkState:
    """Mesh-wide ``fold_in_bucketed``: the whole batch lands on one shard.

    Same math as the single-device bucketed fold-in (d1 through the frozen
    landmarks, new-vs-all scan, back-patch) with the row space
    block-partitioned: the batch is appended *shard-locally* on
    ``target_shard`` (``distributed.sharding.shard_local_append``) and only
    the back-patch merge crosses shards — as an O(bq·k·S) all-gather of
    candidate lists inside :func:`~repro.core.graph.extend_neighbor_graph_sharded`,
    never a gather of the (U, n) representation (jaxpr-checked in
    tests/test_sharded_serving.py). The caller picks ``target_shard`` (the
    serve driver uses least-loaded) and must guarantee
    ``n_valid[target] + bq <= capacity``
    (``lifecycle.buckets.ensure_capacity_sharded``).

    ``b_valid`` and ``target_shard`` are traced, so one executable serves
    every fold-in at a given (capacity, bq) — the PR-3 bucket discipline,
    now per shard. Oracle-exact vs the single-device fold-in modulo the
    dense↔sharded row-id bijection.
    """
    from repro.distributed.sharding import shard_local_append

    from .graph import extend_neighbor_graph_sharded

    st = sstate.state
    bq = new_ratings.shape[0]
    q_valid = (jnp.arange(bq) < b_valid)[:, None]
    new_ratings = jnp.where(q_valid, new_ratings, 0.0)

    if landmarks is None:
        landmarks = st.ratings[st.landmark_idx]  # (n, P) frozen at fit
    new_rep = masked_similarity(new_ratings, landmarks, spec.d1)  # (bq, n)
    new_rep = jnp.where(q_valid, new_rep, 0.0)

    mesh, axes, n_valid = sstate.mesh, sstate.axes, sstate.n_valid
    ratings = shard_local_append(st.ratings, new_ratings, n_valid,
                                 target_shard, mesh, axes)
    rep = shard_local_append(st.representation, new_rep, n_valid,
                             target_shard, mesh, axes)
    # logical ids continue the arrival order: next id == total valid rows
    ranks = jnp.sum(n_valid) + jnp.arange(bq, dtype=jnp.int32)
    row_rank = shard_local_append(sstate.row_rank, ranks, n_valid,
                                  target_shard, mesh, axes)
    graph = extend_neighbor_graph_sharded(
        st.graph, rep, new_rep, n_valid, b_valid, target_shard, mesh,
        spec.d2, row_axes=axes, row_rank=row_rank)
    # pin canonical shardings on the outputs so a state produced by fold-in
    # carries the same layout as one freshly device_put by the bucket driver
    # — otherwise the first fold after a capacity regrow compiles a second
    # executable per (C, bq) just for the provenance difference
    row = jax.sharding.NamedSharding(mesh, P(axes, None))
    row1 = jax.sharding.NamedSharding(mesh, P(axes))
    repl = jax.sharding.NamedSharding(mesh, P())
    pin_row = lambda x: jax.lax.with_sharding_constraint(x, row)
    pin_repl = lambda x: jax.lax.with_sharding_constraint(x, repl)
    return ShardedLandmarkState(
        LandmarkState(
            jax.lax.with_sharding_constraint(
                st.landmark_idx, jax.sharding.NamedSharding(mesh, P(None))),
            pin_row(rep), pin_row(ratings),
            graph=type(st.graph)(pin_row(graph.indices),
                                 pin_row(graph.weights))),
        pin_repl(n_valid.at[target_shard].add(b_valid.astype(jnp.int32))),
        jax.lax.with_sharding_constraint(row_rank, row1),
        mesh, axes)


def predict(state: LandmarkState, users: jax.Array, items: jax.Array,
            spec: LandmarkSpec, *, n_valid=None):
    """Predict the requested (row, col) cells of the oriented matrix.

    ``n_valid`` (graph path only) marks rows >= n_valid as bucket padding —
    their neighbor weights are zeroed inside Eq. (1); see lifecycle.buckets.
    """
    if spec.mode == "item":
        users, items = items, users
    if state.graph is not None:
        return knn.predict_pairs_graph(state.graph, state.ratings, users, items,
                                       n_valid=n_valid)
    return knn.predict_pairs(state.sims, state.ratings, users, items, k=spec.k_neighbors)


def predict_dense(state: LandmarkState, spec: LandmarkSpec) -> jax.Array:
    if state.graph is not None:
        preds = knn.predict_all_graph(state.graph, state.ratings)
    else:
        preds = knn.predict_all(state.sims, state.ratings, k=spec.k_neighbors)
    return preds.T if spec.mode == "item" else preds


# ---------------------------------------------------------------------------
# Baseline (paper Algorithm 1): full-matrix memory-based CF, for comparisons.
# ---------------------------------------------------------------------------


def fit_baseline(matrix: RatingMatrix, measure: str, mode: str = "user") -> LandmarkState:
    """Full-matrix kNN: the O(|U|²·|P|) cost the landmark method removes.

    Keeps the dense sims matrix by construction — it IS the baseline artifact.
    """
    r = _oriented(matrix.ratings, mode)
    sims = full_similarity_matrix(r, measure)
    return LandmarkState(jnp.zeros((0,), jnp.int32), jnp.zeros((r.shape[0], 0)),
                         r, sims=sims)


# ---------------------------------------------------------------------------
# Pod-scale fit: users sharded, landmarks replicated (DESIGN.md §3).
# ---------------------------------------------------------------------------


def fit_distributed(
    key: jax.Array,
    ratings: jax.Array,  # (U, P) global, sharded over user axis
    spec: LandmarkSpec,
    mesh: jax.sharding.Mesh,
    user_axes=("pod", "data"),
    *,
    dense_sims: bool = False,
    chunk_local: int = 512,
) -> LandmarkState:
    """Landmark CF under pjit/shard_map: the d2 step consumes the (U, n)
    representation only, so the sole cross-shard payload is (U, n) — not the
    (U, P) rating exchange the full-matrix baseline would need. The default
    graph build streams candidate chunks (one all-gather of
    chunk_local × n_shards rows per step); fit memory is O(U·(n+k)) per shard
    group instead of O(U²).
    """
    axes = tuple(a for a in user_axes if a in mesh.axis_names)
    user_sharding = jax.sharding.NamedSharding(mesh, P(axes, None))
    rep_sharding = jax.sharding.NamedSharding(mesh, P(axes, None))

    if dense_sims:  # escape hatch: replicate the old O(U²) artifact
        sims_sharding = jax.sharding.NamedSharding(mesh, P(axes, None))

        @partial(
            jax.jit,
            in_shardings=(None, user_sharding),
            out_shardings=(None, rep_sharding, sims_sharding),
        )
        def _fit(key, r):
            idx = select_landmarks(key, r, spec.n_landmarks, spec.selection)
            landmarks = r[idx]  # gather -> replicated (n, P)
            rep = masked_similarity(r, landmarks, spec.d1)  # local GEMMs
            sims = dense_similarity(rep, rep, spec.d2)  # all-gather of (U, n) only
            return idx, rep, sims

        idx, rep, sims = _fit(key, ratings)
        return LandmarkState(idx, rep, ratings, sims=sims)

    import numpy as np

    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    u = ratings.shape[0]
    k = max(1, min(spec.k_neighbors, u - 1))
    # Ragged U: pad rows up to the shard count for the shard_map graph build.
    # Selection runs on the *unpadded* matrix, exactly like the single-device
    # fit — the oracle contract (sharded refresh == from-scratch fit) depends
    # on padding never influencing which rows become landmarks.
    u_per = -(-u // n_shards)
    u_pad = u_per * n_shards
    idx = select_landmarks(key, ratings, spec.n_landmarks, spec.selection)
    landmarks = ratings[idx]  # replicated (n, P)
    r_pad = jnp.pad(ratings, ((0, u_pad - u), (0, 0))) if u_pad != u else ratings

    @partial(jax.jit, in_shardings=(user_sharding, None),
             out_shardings=rep_sharding)
    def _rep(r, lm):
        return masked_similarity(r, lm, spec.d1)  # local GEMMs

    rep = _rep(jax.device_put(r_pad, user_sharding), landmarks)
    with jax.set_mesh(mesh):
        vals, nbrs = jax.jit(
            lambda rp: streaming_knn_graph_sharded(
                rp, mesh, spec.d2, k=k, chunk_local=chunk_local, row_axes=axes,
                exclude_self=True, n_valid=u)
        )(rep)
        graph = jax.jit(finalize_topk)(vals[:u], nbrs[:u])
    return LandmarkState(idx, rep[:u], ratings, graph=graph)
