"""Mesh variant of the write path — mutations on a ShardedLandmarkState.

Same contract as ``repro.mutation.mutate`` (see that module's docstring for
the exactness argument) with the row space block-partitioned over the mesh:

- bitmaps (``tomb``, ``dirty``) and the logical-rank table (``rank_repl``)
  are kept **replicated** — one bool/int32 per row, negligible next to the
  (S*C, P) payload, and replication is what lets every shard mask its own
  candidates and rank any incumbent neighbor without a cross-shard gather.
  ``rank_repl`` mirrors ``ShardedLandmarkState.row_rank`` (which stays
  row-sharded for the fold-in path): exact-weight ties are broken by logical
  arrival order everywhere, so the sharded mutation path stays bit-identical
  to the single-device one (modulo the dense↔sharded id bijection, as for
  fold-in).
- :func:`update_ratings_sharded` — owner-shard-local scatter of the
  re-projected rows (the (S*C, b) back-patch block is a shard-local GEMM:
  row-sharded rep × replicated batch), canonical rank-tie merge into every
  clean row's list.
- :func:`remove_users_sharded` — replicated tomb bits, shard-local zeroing
  of the removed rows, mesh-wide citation eviction (the gathered
  ``tomb[indices]`` / ``rank_repl[indices]`` lookups are replicated-table
  reads — shard-local).
- :func:`repair_sharded` — cross-shard backfill: replicate the (bq, n) dirty
  queries (bounded payload, the fold-in precedent), shard-local masked
  top-k per block, then the PR-4 candidate-list all-gather merge — an
  O(bq·k·S) collective of (value, sharded-id, rank) lists, never a row of
  the representation.
- :func:`compact_tombstones_sharded` — shard-local slot slide at a refresh
  boundary (tombstones never force cross-shard moves), neighbor ids
  remapped through the old→new sharded-id table.

All ids in this module are *sharded* row ids (``shard * C + slot``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.graph import (evict_neighbors, finalize_topk,
                              merge_canonical_topk)
from repro.core.landmark_cf import (LandmarkState, ShardedLandmarkState,
                                    fold_in_sharded)
from repro.core.similarity import dense_similarity, masked_similarity
from repro.core.types import LandmarkSpec, NeighborGraph
from repro.lifecycle import buckets
from repro.mutation.mutate import drain


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MutableStateSharded:
    """A served ``ShardedLandmarkState`` opened for in-place mutation."""

    sstate: ShardedLandmarkState
    landmarks: jax.Array  # (n, P) frozen projection basis, replicated
    tomb: jax.Array  # (S*C,) bool, replicated
    dirty: jax.Array  # (S*C,) bool, replicated
    rank_repl: jax.Array  # (S*C,) int32 logical id per slot, replicated

    def tree_flatten(self):
        return (self.sstate, self.landmarks, self.tomb, self.dirty,
                self.rank_repl), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.sstate.capacity

    @property
    def shard_count(self) -> int:
        return self.sstate.shard_count

    def n_live(self) -> int:
        return self.sstate.total_valid - int(np.asarray(self.tomb).sum())

    def tombstone_frac(self) -> float:
        n = self.sstate.total_valid
        return float(np.asarray(self.tomb).sum()) / n if n else 0.0

    def dirty_count(self) -> int:
        need = np.asarray(self.dirty) & ~np.asarray(self.tomb)
        return int((need & np.asarray(_row_valid_host(self.sstate))).sum())


def _row_valid_host(sstate: ShardedLandmarkState) -> np.ndarray:
    c = sstate.capacity
    gid = np.arange(sstate.shard_count * c)
    return gid % c < np.asarray(sstate.n_valid)[gid // c]


def _row_valid(msst: MutableStateSharded) -> jax.Array:
    """(S*C,) replicated: slot below its shard's fill AND not tombstoned."""
    c = msst.capacity
    gid = jnp.arange(msst.shard_count * c)
    return (gid % c < msst.sstate.n_valid[gid // c]) & ~msst.tomb


def _repl(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _pin(msst: MutableStateSharded, sstate: ShardedLandmarkState,
         tomb, dirty, rank_repl=None) -> MutableStateSharded:
    """Re-assert canonical shardings on the mutable leaves (replicated
    bitmaps/ranks) so repeated mutations keep one executable per shape."""
    repl = _repl(sstate.mesh)
    c = jax.lax.with_sharding_constraint
    return MutableStateSharded(
        sstate, c(msst.landmarks, repl), c(tomb, repl), c(dirty, repl),
        c(msst.rank_repl if rank_repl is None else rank_repl, repl))


def from_sharded(sstate: ShardedLandmarkState) -> MutableStateSharded:
    """Open a sharded state for mutation, freezing the landmark basis and
    replicating the rank table."""
    st = sstate.state
    repl = _repl(sstate.mesh)
    cap = sstate.shard_count * sstate.capacity
    landmarks = jax.device_put(
        np.asarray(st.ratings)[np.asarray(st.landmark_idx)], repl)
    rank = jax.device_put(np.asarray(sstate.row_rank), repl)
    return MutableStateSharded(
        sstate, landmarks,
        jax.device_put(np.zeros((cap,), bool), repl),
        jax.device_put(np.zeros((cap,), bool), repl),
        rank)


def _rebuild(sstate: ShardedLandmarkState, rep, ratings, graph,
             n_valid=None, row_rank=None) -> ShardedLandmarkState:
    mesh, axes = sstate.mesh, sstate.axes
    row = NamedSharding(mesh, P(axes, None))
    row1 = NamedSharding(mesh, P(axes))
    c = jax.lax.with_sharding_constraint
    return ShardedLandmarkState(
        LandmarkState(sstate.state.landmark_idx, c(rep, row), c(ratings, row),
                      graph=NeighborGraph(c(graph.indices, row),
                                          c(graph.weights, row))),
        c(sstate.n_valid if n_valid is None else n_valid, _repl(mesh)),
        c(sstate.row_rank if row_rank is None else row_rank, row1),
        mesh, axes)


# --------------------------------------------------------------------- update
@partial(jax.jit, static_argnames=("spec",))
def update_ratings_sharded(
    msst: MutableStateSharded,
    ids: jax.Array,  # (b,) *sharded* row ids; entries >= b_valid are filler
    rows: jax.Array,  # (b, P) replacement rating rows, replicated
    b_valid: jax.Array,  # () int32
    spec: LandmarkSpec,
) -> MutableStateSharded:
    """``mutate.update_ratings`` on the mesh — see that function for the
    dirty/back-patch split. The scatters land owner-shard-local (an id
    addresses one shard's block); the back-patch block and the canonical
    merge are shard-local by construction (replicated batch, replicated
    bitmaps and rank table); nothing row-sized crosses shards."""
    sstate = msst.sstate
    st = sstate.state
    s, c = msst.shard_count, msst.capacity
    cap = s * c
    ids = ids.astype(jnp.int32)

    valid_slot = (ids >= 0) & (ids < cap) \
        & (ids % c < sstate.n_valid[jnp.clip(ids // c, 0, s - 1)])
    eff = (jnp.arange(ids.shape[0]) < b_valid) & valid_slot \
        & ~msst.tomb[jnp.clip(ids, 0, cap - 1)]
    safe_ids = jnp.where(eff, ids, cap)

    rows = jnp.where(eff[:, None], rows, 0.0)
    new_rep = masked_similarity(rows, msst.landmarks, spec.d1)
    new_rep = jnp.where(eff[:, None], new_rep, 0.0)

    ratings = st.ratings.at[safe_ids].set(rows, mode="drop")
    rep = st.representation.at[safe_ids].set(new_rep, mode="drop")

    changed = jnp.zeros((cap,), bool).at[safe_ids].set(eff, mode="drop")
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    row_valid = _row_valid(msst)
    victim = jnp.any(changed[graph.indices], axis=1)
    inert_row = jnp.any((graph.indices == 0) & (graph.weights == 0.0), axis=1)
    dirty = msst.dirty | (row_valid & (changed | victim | inert_row))

    back = dense_similarity(rep, new_rep, spec.d2)  # (S*C, b) local GEMM
    col_ok = eff[None, :] & (jnp.arange(cap)[:, None] != safe_ids[None, :])
    back = jnp.where(col_ok, back, -jnp.inf)
    # ties break by logical rank, not sharded id — the sharded canon:
    # columns are permuted rank-ascending so ``lax.top_k``'s positional
    # tie-break is the canonical order, then the ≤k surviving candidates
    # merge into the incumbent list by rank-count — no full-width sort.
    cand = jnp.where(eff, ids, 0)
    cand_rank = msst.rank_repl[cand]
    order = jnp.argsort(jnp.where(eff, cand_rank, jnp.iinfo(jnp.int32).max))
    bv, bsel = jax.lax.top_k(back[:, order], min(graph.k, ids.shape[0]))
    pv, pi = merge_canonical_topk(
        graph.weights, graph.indices, bv, cand[order][bsel], graph.k,
        a_rank=msst.rank_repl[graph.indices], b_rank=cand_rank[order][bsel])
    patched = finalize_topk(pv, pi)
    patch = (row_valid & ~dirty)[:, None]
    graph = NeighborGraph(jnp.where(patch, patched.indices, graph.indices),
                          jnp.where(patch, patched.weights, graph.weights))
    return _pin(msst, _rebuild(sstate, rep, ratings, graph), msst.tomb, dirty)


# --------------------------------------------------------------------- remove
@jax.jit
def remove_users_sharded(
    msst: MutableStateSharded,
    ids: jax.Array,  # (b,) *sharded* row ids; entries >= b_valid are filler
    b_valid: jax.Array,  # () int32
) -> MutableStateSharded:
    """``mutate.remove_users`` on the mesh: replicated tomb bits, shard-local
    GDPR zeroing, mesh-wide eviction of every citation (rank-canonical), the
    victims dirty. Per-shard fills are untouched (append high-water marks)."""
    sstate = msst.sstate
    st = sstate.state
    s, c = msst.shard_count, msst.capacity
    cap = s * c
    ids = ids.astype(jnp.int32)

    valid_slot = (ids >= 0) & (ids < cap) \
        & (ids % c < sstate.n_valid[jnp.clip(ids // c, 0, s - 1)])
    eff = (jnp.arange(ids.shape[0]) < b_valid) & valid_slot \
        & ~msst.tomb[jnp.clip(ids, 0, cap - 1)]
    safe_ids = jnp.where(eff, ids, cap)

    tomb = msst.tomb.at[safe_ids].set(True, mode="drop")
    b = ids.shape[0]
    ratings = st.ratings.at[safe_ids].set(
        jnp.zeros((b, st.ratings.shape[1]), st.ratings.dtype), mode="drop")
    rep = st.representation.at[safe_ids].set(
        jnp.zeros((b, st.representation.shape[1]),
                  st.representation.dtype), mode="drop")

    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    graph, hit = evict_neighbors(graph, tomb, row_rank=msst.rank_repl)
    gid = jnp.arange(cap)
    row_valid = (gid % c < sstate.n_valid[gid // c]) & ~tomb
    dirty = msst.dirty | (hit & row_valid)
    k = graph.k
    gi = graph.indices.at[safe_ids].set(jnp.zeros((b, k), jnp.int32),
                                        mode="drop")
    gw = graph.weights.at[safe_ids].set(jnp.zeros((b, k), jnp.float32),
                                        mode="drop")
    dirty = dirty.at[safe_ids].set(False, mode="drop")
    return _pin(msst, _rebuild(sstate, rep, ratings,
                               NeighborGraph(gi, gw)), tomb, dirty)


# --------------------------------------------------------------------- repair
@partial(jax.jit, static_argnames=("bq", "spec_d2"))
def repair_sharded(
    msst: MutableStateSharded,
    bq: int,
    spec_d2: str,
) -> Tuple[MutableStateSharded, jax.Array]:
    """Cross-shard backfill of up to ``bq`` dirty rows; returns
    ``(state, n_repaired)``.

    The dirty queries' representations are replicated — a (bq, n) payload,
    the same bound as a fold-in batch — then each shard takes a masked local
    top-k over its own block and the lists merge through the PR-4 all-gather
    (values + sharded ids + logical ranks, O(bq·k·S) bytes). Local positional
    ties equal local rank order (slots append in logical order and
    compaction preserves it), and the merge re-sorts by rank, so the result
    is the canonical list an oracle build would produce.
    """
    from jax import shard_map

    from repro.distributed.sharding import shard_linear_index

    sstate = msst.sstate
    st = sstate.state
    mesh, axes = sstate.mesh, sstate.axes
    s, c = msst.shard_count, msst.capacity
    cap = s * c
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    k = graph.k
    kk = min(k, c)

    need = msst.dirty & _row_valid(msst)
    order = jnp.where(need, jnp.arange(cap, dtype=jnp.int32), cap)
    sel = jnp.sort(order)[:bq]
    active = sel < cap
    safe = jnp.minimum(sel, cap - 1)
    queries = jax.lax.with_sharding_constraint(
        st.representation[safe], _repl(mesh))  # (bq, n) replicated

    def inner(rep_l, rank_l, queries, n_valid, tomb, sel):
        lin = shard_linear_index(mesh, axes)
        slot = jnp.arange(c)
        base = lin * c
        sims = dense_similarity(queries, rep_l, spec_d2)  # (bq, C)
        tomb_l = jax.lax.dynamic_slice_in_dim(tomb, base, c)
        invalid = ((slot >= n_valid[lin]) | tomb_l)[None, :] \
            | ((base + slot)[None, :] == sel[:, None])
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, kk)  # ties -> lowest slot == lowest rank
        g = base + i
        r = rank_l[i]
        vs = jax.lax.all_gather(v, axes, axis=1, tiled=True)  # (bq, kk*S)
        gs = jax.lax.all_gather(g, axes, axis=1, tiled=True)
        rs = jax.lax.all_gather(r, axes, axis=1, tiled=True)
        ord1 = jnp.argsort(rs, axis=1)
        vs1 = jnp.take_along_axis(vs, ord1, axis=1)
        gs1 = jnp.take_along_axis(gs, ord1, axis=1)
        sel2 = jnp.argsort(-vs1, axis=1)[:, :k]
        return (jnp.take_along_axis(vs1, sel2, axis=1),
                jnp.take_along_axis(gs1, sel2, axis=1))

    row = P(axes, None)
    vals, idx = shard_map(
        inner, mesh=mesh,
        in_specs=(row, P(axes), P(None, None), P(None), P(None), P(None)),
        out_specs=(P(None, None), P(None, None)), check_vma=False,
    )(st.representation, sstate.row_rank, queries, sstate.n_valid,
      msst.tomb, sel)
    fixed = finalize_topk(vals, idx)
    gi = graph.indices.at[sel].set(fixed.indices, mode="drop")
    gw = graph.weights.at[sel].set(fixed.weights, mode="drop")
    dirty = msst.dirty.at[sel].set(False, mode="drop")
    out = _pin(msst, _rebuild(sstate, st.representation, st.ratings,
                              NeighborGraph(gi, gw)), msst.tomb, dirty)
    return out, jnp.sum(active.astype(jnp.int32))


def drain_repairs_sharded(msst: MutableStateSharded, spec: LandmarkSpec,
                          bq: int = 64) -> MutableStateSharded:
    """Host driver: run :func:`repair_sharded` until no dirty rows remain,
    with the single-device drain's spans and counters
    (:func:`repro.mutation.mutate.drain`)."""
    return drain(msst, lambda m: repair_sharded(m, bq, spec.d2)[0])


# ------------------------------------------------------------------ lifecycle
def compact_tombstones_sharded(msst: MutableStateSharded
                               ) -> MutableStateSharded:
    """Physically drop tombstoned rows, shard-locally (refresh boundary).

    Within each shard block, live slots slide down in slot order (which is
    logical-rank order, so canonical tie-breaking survives); per-shard fills
    shrink; neighbor ids remap through the old→new sharded-id table. Rows
    never change owner shard — rebalancing stays the refresh/repack policy's
    job. Requires a drained dirty bitmap.
    """
    from repro import obs as obslib

    assert msst.dirty_count() == 0, "drain repairs before compacting"
    sstate = msst.sstate
    st = sstate.state
    s, c = msst.shard_count, msst.capacity
    tomb = np.asarray(msst.tomb)
    n_valid = np.asarray(sstate.n_valid)
    gid = np.arange(s * c)
    live = (gid % c < n_valid[gid // c]) & ~tomb
    with obslib.span("compact", cat="mutation",
                     args={"dropped": int((~live & tomb).sum())}):
        return _compact_sharded_body(msst, sstate, st, s, c, live)


def _compact_sharded_body(msst, sstate, st, s, c, live):

    table = np.zeros((s * c,), np.int32)
    new_valid = np.zeros((s,), np.int32)
    src = np.full((s * c,), -1, np.int64)
    for sh in range(s):
        blk = np.arange(sh * c, (sh + 1) * c)
        alive = blk[live[blk]]
        new_valid[sh] = len(alive)
        table[alive] = sh * c + np.arange(len(alive), dtype=np.int32)
        src[sh * c: sh * c + len(alive)] = alive

    take = np.maximum(src, 0)
    keep = (src >= 0)

    def gather(x):
        x = np.asarray(x)
        out = np.zeros_like(x)
        out[keep] = x[take[keep]]
        return out

    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    graph = graph.remap(jnp.asarray(table))
    mesh, axes = sstate.mesh, sstate.axes
    from repro.distributed.sharding import cf_row_sharding

    row2 = cf_row_sharding(mesh, axes, ndim=2)
    row1 = cf_row_sharding(mesh, axes, ndim=1)
    repl = _repl(mesh)
    new_sstate = ShardedLandmarkState(
        LandmarkState(st.landmark_idx,
                      jax.device_put(gather(st.representation), row2),
                      jax.device_put(gather(st.ratings), row2),
                      graph=NeighborGraph(
                          jax.device_put(gather(graph.indices), row2),
                          jax.device_put(gather(graph.weights), row2))),
        jax.device_put(new_valid, repl),
        jax.device_put(gather(sstate.row_rank), row1),
        mesh, axes)
    return MutableStateSharded(
        new_sstate,
        msst.landmarks,
        jax.device_put(np.zeros((s * c,), bool), repl),
        jax.device_put(np.zeros((s * c,), bool), repl),
        jax.device_put(gather(msst.rank_repl), repl))


# -------------------------------------------------------------------- fold-in
def fold_in_rows_sharded(msst: MutableStateSharded, rows, bq: int,
                         spec: LandmarkSpec, min_bucket: int = 32,
                         growth: float = buckets.DEFAULT_GROWTH):
    """Mutation-aware sharded fold-in driver — ``buckets.fold_in_rows_sharded``
    with the frozen basis, bitmap regrowth across capacity changes, and a
    post-append eviction pass (the sharded extend's masks are fill-based, so
    a tombstoned slot below the fill mark could be cited by a new row).
    Returns ``(msst, shards, slots)`` like the bucketed driver."""
    sstate = msst.sstate
    n = len(rows)
    p = sstate.state.ratings.shape[1]
    rows = jnp.asarray(rows)
    shards = np.zeros(n, np.int32)
    slots = np.zeros(n, np.int32)
    for lo in range(0, n, bq):
        chunk = rows[lo:lo + bq]
        m = chunk.shape[0]
        fills = np.asarray(sstate.n_valid)
        target = int(np.argmin(fills))
        old_cap = sstate.capacity
        sstate, grew = buckets.ensure_capacity_sharded(
            sstate, target, bq, min_bucket, growth)
        if grew:
            msst = _regrow_masks(msst, sstate, old_cap)
        shards[lo:lo + m] = target
        slots[lo:lo + m] = int(fills[target]) + np.arange(m)
        padded = jnp.zeros((bq, p), jnp.float32).at[:m].set(chunk)
        base = int(np.asarray(sstate.n_valid).sum())
        sstate = fold_in_sharded(sstate, padded, jnp.int32(m),
                                 jnp.int32(target), spec,
                                 landmarks=msst.landmarks)
        msst = _absorb_fold(msst, sstate, target, int(fills[target]), m,
                            base)
        sstate = msst.sstate
    return msst, shards, slots


def _regrow_masks(msst: MutableStateSharded, sstate: ShardedLandmarkState,
                  old_cap: int) -> MutableStateSharded:
    """Re-express the replicated bitmaps/ranks after a per-shard regrow."""
    s = msst.shard_count
    new_cap = sstate.capacity
    pad = [(0, 0), (0, new_cap - old_cap)]
    grow = lambda x: jnp.pad(np.asarray(x).reshape(s, old_cap), pad) \
        .reshape(s * new_cap)
    repl = _repl(sstate.mesh)
    return MutableStateSharded(
        sstate, msst.landmarks,
        jax.device_put(grow(msst.tomb), repl),
        jax.device_put(grow(msst.dirty), repl),
        jax.device_put(grow(msst.rank_repl), repl))


@jax.jit
def _post_fold_evict(msst: MutableStateSharded) -> MutableStateSharded:
    sstate = msst.sstate
    st = sstate.state
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    graph, hit = evict_neighbors(graph, msst.tomb, row_rank=msst.rank_repl)
    dirty = msst.dirty | (hit & _row_valid(msst))
    return _pin(msst, _rebuild(sstate, st.representation, st.ratings, graph),
                msst.tomb, dirty)


def _absorb_fold(msst: MutableStateSharded, sstate: ShardedLandmarkState,
                 target: int, slot0: int, m: int, rank0: int
                 ) -> MutableStateSharded:
    """Track one fold-in batch: extend the replicated rank table with the
    new rows' logical ids, then evict any tombstoned citations the
    fill-masked extend let through."""
    c = sstate.capacity
    rank = np.asarray(msst.rank_repl).copy()
    rank[target * c + slot0: target * c + slot0 + m] = \
        rank0 + np.arange(m, dtype=np.int32)
    msst = MutableStateSharded(
        sstate, msst.landmarks, msst.tomb, msst.dirty,
        jax.device_put(rank, _repl(sstate.mesh)))
    return _post_fold_evict(msst)


# ------------------------------------------------------------------- serving
def predict_pairs(msst: MutableStateSharded, users, items):
    from repro.core import knn

    sstate = msst.sstate
    return knn.predict_pairs_graph(sstate.state.graph, sstate.state.ratings,
                                   users, items, n_valid=sstate.n_valid,
                                   shard_cap=sstate.capacity, tomb=msst.tomb)


def recommend_topn(msst: MutableStateSharded, users, n: int = 10):
    from repro.core import knn

    sstate = msst.sstate
    return knn.recommend_topn_graph(sstate.state.graph, sstate.state.ratings,
                                    users, n=n, n_valid=sstate.n_valid,
                                    shard_cap=sstate.capacity, tomb=msst.tomb)
