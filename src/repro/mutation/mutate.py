"""Write-path mutations on a served landmark-CF state — updates, GDPR
deletion, and decremental neighbor-graph repair (docs/mutation.md).

Every prior serve path (fold-in, buckets, IVF append, engine fold lane) is
append-only. Real CF traffic re-rates items, un-rates them, and deletes
accounts — the maintenance problem of Lu & Shen (1505.07900), which the
paper's landmark projection makes tractable: a changed user only needs its
d1 row re-projected through the *frozen* landmarks, never a global
similarity recompute. This module closes that write path on a single
device; ``repro.mutation.sharded`` is the mesh variant.

Design (all fixed-shape, jit-compiled once per (capacity, batch) pair):

- :class:`MutableState` wraps a ``BucketedState`` with two (capacity,) bool
  bitmaps — ``tomb`` (tombstoned rows) and ``dirty`` (rows whose neighbor
  list needs a rescan) — plus a frozen (n, P) snapshot of the landmark
  rating rows. The snapshot is the projection basis: updating or deleting a
  landmark *user* must not shift every other user's representation, so the
  basis stays frozen until the next refresh re-selects landmarks (the
  refresh is also where a deleted landmark's ratings leave the basis).
- :func:`update_ratings` re-projects the changed rows through the frozen
  landmarks, scatters ratings + representation in place, and splits the
  graph work: rows *citing* a changed user are marked dirty (their stale
  weight — and, worse, their unknown old (k+1)-th candidate — needs a
  rescan), every other live row gets the changed users merged into its list
  by a canonical (value desc, id asc) lexicographic merge
  (``core.graph.merge_canonical_topk`` — the batch columns are permuted
  id-ascending so positional ``top_k`` tie-breaks canonically, then the
  two sorted lists merge by rank-count; a plain positional ``top_k`` over
  the concat would misorder exact-weight ties because a changed id can be
  smaller than list ids, and a full-width argsort is the write path's
  latency bottleneck).
  Peak extra memory is the (capacity, b) back-patch block — the same
  skinny block ``extend_neighbor_graph`` uses; no (U, U) or
  (U, n)·(n, U) product exists (jaxpr-checked in tests/test_mutation.py).
- :func:`remove_users` sets tomb bits, zeroes the removed rows' ratings and
  representation device-side (the data is erased, not merely hidden),
  evicts every citation of a removed id (``core.graph.evict_neighbors``)
  and marks the victim rows dirty. Tombstoned rows are additionally masked
  out of every consumer (``knn`` via the ``tomb`` gather,
  ``retrieval.search`` via posting-list masks, the router) — absence from
  results never waits on the repair.
- :func:`repair` drains up to ``bq`` dirty rows per call: a full masked
  rescan over the valid prefix (chunked (bq, chunk) sims tiles — the same
  schedule as ``_bucketed_query_topk``) or sublinear IVF candidate
  generation when an index is supplied (exact at full probe). One warm
  executable per (capacity, bq), never a compile per event.
- :func:`compact_tombstones` swaps tombstones out physically at a refresh
  boundary: live rows slide down in id order, neighbor ids remap through
  the monotone old→new table (``NeighborGraph.remap``), bitmaps reset.

Exactness bar (tests/test_mutation.py, tests/test_properties.py): after
repairs drain, the state is **bitwise** equal to a from-scratch ``fit`` on
the mutated matrix with the same frozen landmark basis, for all three d2
measures — similarity values are row-pair-local (per-row norms / means /
sq-norms), so re-projection and patching reproduce the oracle's floats
exactly, and the canonical tie-break reproduces its top-k selection.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knn
from repro.core.graph import (evict_neighbors, finalize_topk,
                              merge_canonical_topk)
from repro.core.landmark_cf import LandmarkState
from repro.core.similarity import dense_similarity, masked_similarity
from repro.core.types import LandmarkSpec, NeighborGraph
from repro.lifecycle import buckets


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MutableState:
    """A served ``BucketedState`` opened for in-place mutation.

    ``tomb[i]`` — row i is deleted: masked out of every consumer, physically
    removed at the next :func:`compact_tombstones`. ``dirty[i]`` — row i's
    neighbor list lost an entry (or belongs to a changed user) and needs a
    :func:`repair` rescan before the exactness bar holds again.
    ``landmarks`` is the frozen (n, P) projection basis (see module doc).
    """

    bstate: buckets.BucketedState
    landmarks: jax.Array  # (n, P) frozen landmark rating rows
    tomb: jax.Array  # (capacity,) bool
    dirty: jax.Array  # (capacity,) bool

    def tree_flatten(self):
        return (self.bstate, self.landmarks, self.tomb, self.dirty), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.bstate.capacity

    @property
    def k(self) -> int:
        return self.bstate.k

    @property
    def n_valid(self) -> jax.Array:
        """High-water append mark — tombstoned rows still count until
        compaction (live rows = ``n_valid - tomb.sum()``)."""
        return self.bstate.n_valid

    def n_live(self) -> int:
        return int(self.bstate.n_valid) - int(np.asarray(self.tomb).sum())

    def tombstone_frac(self) -> float:
        """Fraction of the valid prefix that is tombstoned — the lifecycle
        policy's compaction signal (``policy.should_compact_tombstones``)."""
        n = int(self.bstate.n_valid)
        return float(np.asarray(self.tomb).sum()) / n if n else 0.0

    def dirty_count(self) -> int:
        need = np.asarray(self.dirty) & ~np.asarray(self.tomb)
        return int(need[: int(self.bstate.n_valid)].sum())


def from_bucketed(bstate: buckets.BucketedState) -> MutableState:
    """Open a bucketed state for mutation, freezing the landmark basis."""
    st = bstate.state
    cap = bstate.capacity
    return MutableState(
        bstate,
        jnp.asarray(st.ratings[st.landmark_idx, :bstate.n_items]),
        jnp.zeros((cap,), bool),
        jnp.zeros((cap,), bool),
    )


def from_fitted(state: LandmarkState,
                min_bucket: int = buckets.DEFAULT_MIN_BUCKET,
                growth: float = buckets.DEFAULT_GROWTH) -> MutableState:
    """Wrap a freshly fitted state (convenience for tests/benchmarks)."""
    return from_bucketed(buckets.from_state(state, min_bucket, growth))


def _grow_masks(mst: MutableState, bstate: buckets.BucketedState
                ) -> MutableState:
    """Re-wrap after a capacity regrow: pad the bitmaps with False."""
    pad = bstate.capacity - mst.tomb.shape[0]
    if pad <= 0:
        return MutableState(bstate, mst.landmarks, mst.tomb, mst.dirty)
    return MutableState(bstate, mst.landmarks,
                        jnp.pad(mst.tomb, (0, pad)),
                        jnp.pad(mst.dirty, (0, pad)))


# --------------------------------------------------------------------- update
@partial(jax.jit, static_argnames=("spec",))
def update_ratings(
    mst: MutableState,
    ids: jax.Array,  # (b,) row ids to replace; entries >= b_valid are filler
    rows: jax.Array,  # (b, n_items) full replacement rows (0 == un-rated)
    b_valid: jax.Array,  # () int32 real entries in the batch
    spec: LandmarkSpec,
) -> MutableState:
    """Replace ``b_valid`` users' rating rows in place (re-rate + un-rate).

    The replacement row is the user's complete new rating vector — zero
    entries un-rate. Ids must be unique within a batch (the host drivers
    deduplicate); updates addressed at tombstoned or out-of-range ids are
    dropped. Compiles once per (capacity, b) pair.

    Graph maintenance: the changed rows and every row citing them go dirty
    (full rescan in :func:`repair`); all other live rows get the changed
    users canonically merged into their lists here — exact because a row
    not citing a changed id holds the true top-k of the *other* candidates,
    so merging the changed users' fresh similarities reproduces the oracle
    top-k. Rows holding an inert (0, 0.0) slot also go dirty instead of
    merging: the stored zero would shadow a genuinely negative new
    similarity.
    """
    bst = mst.bstate
    st = bst.state
    cap, b = bst.capacity, ids.shape[0]
    n_valid = bst.n_valid
    ids = ids.astype(jnp.int32)

    eff = ((jnp.arange(b) < b_valid) & (ids >= 0) & (ids < n_valid)
           & ~mst.tomb[jnp.clip(ids, 0, cap - 1)])
    safe_ids = jnp.where(eff, ids, cap)  # cap == out-of-bounds drop

    rows = jnp.where(eff[:, None], rows, 0.0)
    new_rep = masked_similarity(rows, mst.landmarks, spec.d1)  # (b, n)
    new_rep = jnp.where(eff[:, None], new_rep, 0.0)

    ratings = st.ratings.at[safe_ids].set(
        buckets.pad_items(rows, st.ratings.shape[1]), mode="drop")
    rep = st.representation.at[safe_ids].set(new_rep, mode="drop")

    changed = jnp.zeros((cap,), bool).at[safe_ids].set(eff, mode="drop")
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    row_valid = (jnp.arange(cap) < n_valid) & ~mst.tomb
    victim = jnp.any(changed[graph.indices], axis=1)
    inert_row = jnp.any((graph.indices == 0) & (graph.weights == 0.0), axis=1)
    dirty = mst.dirty | (row_valid & (changed | victim | inert_row))

    # back-patch every clean live row with the changed users' fresh sims —
    # the (capacity, b) skinny block. Columns are permuted id-ascending so
    # ``lax.top_k``'s positional tie-break IS the canonical id-asc order
    # (the graph-build invariant), then the ≤k surviving candidates merge
    # into the incumbent list by rank-count — no full-width sort.
    back = dense_similarity(rep, new_rep, spec.d2)  # (cap, b)
    col_ok = eff[None, :] & (jnp.arange(cap)[:, None] != safe_ids[None, :])
    back = jnp.where(col_ok, back, -jnp.inf)
    order = jnp.argsort(safe_ids)  # effective ids ascending, dropped last
    cand = jnp.where(eff, ids, 0)[order]
    bv, bsel = jax.lax.top_k(back[:, order], min(graph.k, b))
    pv, pi = merge_canonical_topk(graph.weights, graph.indices,
                                  bv, cand[bsel], graph.k)
    patched = finalize_topk(pv, pi)
    patch = (row_valid & ~dirty)[:, None]
    graph = NeighborGraph(jnp.where(patch, patched.indices, graph.indices),
                          jnp.where(patch, patched.weights, graph.weights))

    return MutableState(
        buckets.BucketedState(
            LandmarkState(st.landmark_idx, rep, ratings, graph=graph),
            n_valid, bst.n_items),
        mst.landmarks, mst.tomb, dirty)


# --------------------------------------------------------------------- remove
@jax.jit
def remove_users(
    mst: MutableState,
    ids: jax.Array,  # (b,) row ids to tombstone; entries >= b_valid filler
    b_valid: jax.Array,  # () int32 real entries in the batch
) -> MutableState:
    """Tombstone ``b_valid`` users (GDPR deletion). Device-side effects, all
    in one compiled step per (capacity, b):

    - tomb bits set; the rows' ratings and representation are **zeroed**
      (erased, not hidden — only the tombstoned graph citations linger
      until eviction below, and those hold no rating data);
    - every citation of a removed id is evicted from every neighbor list
      (``evict_neighbors``), so no returned neighbor list contains a
      tombstoned id even before repair;
    - victim rows (those that lost an entry) go dirty — their (k+1)-th
      candidate was never stored, so only a rescan restores exactness;
    - the removed rows' own lists become inert and their dirty bits clear.

    ``n_valid`` is untouched (it is the append high-water mark); live count
    and ``tombstone_frac`` derive from the bitmap until compaction.
    """
    bst = mst.bstate
    st = bst.state
    cap, b = bst.capacity, ids.shape[0]
    n_valid = bst.n_valid
    ids = ids.astype(jnp.int32)

    eff = ((jnp.arange(b) < b_valid) & (ids >= 0) & (ids < n_valid)
           & ~mst.tomb[jnp.clip(ids, 0, cap - 1)])
    safe_ids = jnp.where(eff, ids, cap)

    tomb = mst.tomb.at[safe_ids].set(True, mode="drop")
    zero_r = jnp.zeros((b, st.ratings.shape[1]), st.ratings.dtype)
    zero_p = jnp.zeros((b, st.representation.shape[1]),
                       st.representation.dtype)
    ratings = st.ratings.at[safe_ids].set(zero_r, mode="drop")
    rep = st.representation.at[safe_ids].set(zero_p, mode="drop")

    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    graph, hit = evict_neighbors(graph, tomb)
    row_valid = (jnp.arange(cap) < n_valid) & ~tomb
    dirty = (mst.dirty | (hit & row_valid))
    # removed rows: inert lists, no repair owed
    k = graph.k
    gi = graph.indices.at[safe_ids].set(jnp.zeros((b, k), jnp.int32),
                                        mode="drop")
    gw = graph.weights.at[safe_ids].set(jnp.zeros((b, k), jnp.float32),
                                        mode="drop")
    dirty = dirty.at[safe_ids].set(False, mode="drop")

    return MutableState(
        buckets.BucketedState(
            LandmarkState(st.landmark_idx, rep, ratings,
                          graph=NeighborGraph(gi, gw)),
            n_valid, bst.n_items),
        mst.landmarks, tomb, dirty)


# --------------------------------------------------------------------- repair
def _rescan_topk(
    queries: jax.Array,  # (bq, n) dirty rows' representations
    cand_src: jax.Array,  # (capacity, n) all rows
    measure: str,
    k: int,
    chunk: int,
    n_valid: jax.Array,  # () int32
    tomb: jax.Array,  # (capacity,) bool
    self_gid: jax.Array,  # (bq,) row id of each query (capacity == inactive)
) -> Tuple[jax.Array, jax.Array]:
    """Masked full rescan: top-k over the live prefix, (bq, chunk) tiles.

    Candidates are laid out in ascending-id order, so ``top_k``'s positional
    tie-break is the canonical id-ascending tie-break of every fit build —
    the rescanned rows come back bitwise equal to a from-scratch build."""
    bq = queries.shape[0]
    c = cand_src.shape[0]
    chunk = max(min(chunk, c), min(k, c))
    n_chunks = -(-c // chunk)
    pad = n_chunks * chunk - c
    if pad:
        cand_src = jnp.pad(cand_src, ((0, pad), (0, 0)))
        tomb = jnp.pad(tomb, (0, pad), constant_values=True)

    def body(carry, c_idx):
        best_v, best_i = carry
        cand = jax.lax.dynamic_slice_in_dim(cand_src, c_idx * chunk, chunk,
                                            axis=0)
        sims = dense_similarity(queries, cand, measure)  # (bq, chunk)
        cand_ids = c_idx * chunk + jnp.arange(chunk)
        dead = jax.lax.dynamic_slice_in_dim(tomb, c_idx * chunk, chunk)
        invalid = ((cand_ids >= n_valid) | dead)[None, :] \
            | (cand_ids[None, :] == self_gid[:, None])
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, k)
        mv = jnp.concatenate([best_v, v], axis=1)
        mi = jnp.concatenate([best_i, (i + c_idx * chunk).astype(jnp.int32)],
                             axis=1)
        nv, sel = jax.lax.top_k(mv, k)
        return (nv, jnp.take_along_axis(mi, sel, axis=1)), None

    init = (jnp.full((bq, k), -jnp.inf, jnp.float32),
            jnp.zeros((bq, k), jnp.int32))
    (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return vals, idx


def repair(
    mst: MutableState,
    bq: int,
    spec: LandmarkSpec,
    *,
    chunk: int = 4096,
    ivf_index=None,  # live retrieval.IVFIndex over the rows (optional)
    nprobe: Optional[int] = None,
) -> Tuple[MutableState, jax.Array]:
    """Rebuild up to ``bq`` dirty rows' neighbor lists; returns
    ``(state, n_repaired)``.

    The lowest-id dirty rows are selected in-trace from the bitmap (a sort
    over (capacity,) ids — fixed shape, so one warm executable per
    (capacity, bq) serves every repair, the bucket discipline of PR 3).
    With an ``ivf_index`` the rescan probes only the ``nprobe`` nearest
    cells — O(bq·(U/C)·nprobe·n) candidate generation, exact at full probe;
    without one it is a chunked full scan over the live prefix. Tombstoned
    candidates are masked either way.

    The compiled step returns only the neighbour lists and the dirty map;
    the new state shares every other array with ``mst``. A jitted function
    copies each array it returns, and a copy of the rating matrix per
    round would hold a third matrix on the device during a write.
    """
    graph, dirty, n = _repair_lists(mst, bq, spec, chunk=chunk,
                                    ivf_index=ivf_index, nprobe=nprobe)
    bst = mst.bstate
    st = bst.state
    out = MutableState(
        buckets.BucketedState(
            LandmarkState(st.landmark_idx, st.representation, st.ratings,
                          graph=graph),
            bst.n_valid, bst.n_items),
        mst.landmarks, mst.tomb, dirty)
    return out, n


@partial(jax.jit, static_argnames=("bq", "spec", "chunk", "nprobe"))
def _repair_lists(mst: MutableState, bq: int, spec: LandmarkSpec, *,
                  chunk: int, ivf_index, nprobe: Optional[int]):
    """:func:`repair`'s compiled step: ``(graph, dirty, n_repaired)``."""
    bst = mst.bstate
    st = bst.state
    cap = bst.capacity
    n_valid = bst.n_valid
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    k = graph.k

    need = mst.dirty & ~mst.tomb & (jnp.arange(cap) < n_valid)
    order = jnp.where(need, jnp.arange(cap, dtype=jnp.int32), cap)
    sel = jnp.sort(order)[:bq]  # ascending dirty ids, cap == padding
    active = sel < cap
    safe = jnp.minimum(sel, cap - 1)
    queries = st.representation[safe]  # (bq, n)

    if ivf_index is not None:
        from repro.retrieval import search

        np_ = ivf_index.n_clusters if nprobe is None else nprobe
        vals, idx = search(ivf_index, queries, k, np_, spec.d2,
                           self_ids=sel, tomb=mst.tomb)
        # drop candidates above the live prefix (index may hold stale slots)
        vals = jnp.where(idx < n_valid, vals, -jnp.inf)
        vals, si = jax.lax.top_k(vals, k)
        idx = jnp.take_along_axis(idx, si, axis=1)
    else:
        vals, idx = _rescan_topk(queries, st.representation, spec.d2, k,
                                 chunk, n_valid, mst.tomb, sel)
    fixed = finalize_topk(vals, idx)
    gi = graph.indices.at[sel].set(fixed.indices, mode="drop")
    gw = graph.weights.at[sel].set(fixed.weights, mode="drop")
    dirty = mst.dirty.at[sel].set(False, mode="drop")
    return NeighborGraph(gi, gw), dirty, jnp.sum(active.astype(jnp.int32))


def drain_repairs(mst: MutableState, spec: LandmarkSpec, bq: int = 64,
                  *, chunk: int = 4096, ivf_index=None,
                  nprobe: Optional[int] = None) -> MutableState:
    """Host driver: run :func:`repair` until the dirty bitmap is empty
    (:func:`drain`)."""
    return drain(mst, lambda m: repair(m, bq, spec, chunk=chunk,
                                       ivf_index=ivf_index,
                                       nprobe=nprobe)[0])


def drain(mst, repair_once):
    """Apply ``repair_once`` until ``mst.dirty_count()`` is 0; shared by
    the single-device and the sharded drain.

    Under the current :mod:`repro.obs` instance (the engine's, scoped to
    its write lane, or the installed one) the drain is one ``repair.drain``
    span holding one ``repair.round`` span per call, each with the rows it
    fixed, and the totals land on the ``mutation.repaired_rows`` and
    ``mutation.repair_rounds`` counters. Each round ends on the host's
    read of the dirty count, which waits for the round on the device."""
    from repro import obs as obslib

    left = n0 = int(mst.dirty_count())
    rounds = 0
    with obslib.span("repair.drain", cat="mutation", args={"rows": n0}):
        while left > 0:
            args = {}
            with obslib.span("repair.round", cat="mutation", args=args):
                mst = repair_once(mst)
                before, left = left, int(mst.dirty_count())
                args["rows"] = before - left
            rounds += 1
    o = obslib.current()
    if o is not None and o.enabled and n0:
        o.registry.counter("mutation.repaired_rows").inc(n0)
        o.registry.counter("mutation.repair_rounds").inc(rounds)
    return mst


# ------------------------------------------------------------------ lifecycle
def compact_tombstones(mst: MutableState) -> MutableState:
    """Physically remove tombstoned rows (the refresh-boundary compaction).

    Live rows slide down preserving id order; neighbor ids remap through
    the monotone old→new table (``NeighborGraph.remap`` — monotonicity
    preserves the canonical tie order, so the compacted graph is bitwise a
    from-scratch build on the compacted matrix). Requires a drained dirty
    bitmap — compacting unrepaired rows would freeze their staleness in.
    Host-side by design: it runs at a refresh/swap boundary, not on the
    request path, and keeps the bucket capacity (no recompiles).
    """
    from repro import obs as obslib

    assert mst.dirty_count() == 0, "drain repairs before compacting"
    bst = mst.bstate
    st = bst.state
    cap = bst.capacity
    n_valid = int(bst.n_valid)
    tomb = np.asarray(mst.tomb)
    with obslib.span("compact", cat="mutation",
                     args={"dropped": int(tomb[:n_valid].sum())}):
        return _compact_tombstones_body(mst, bst, st, cap, n_valid, tomb)


def _compact_tombstones_body(mst, bst, st, cap, n_valid, tomb):
    live = ~tomb & (np.arange(cap) < n_valid)
    src = np.nonzero(live)[0]  # ascending — order-preserving
    n_live = len(src)
    table = np.zeros((cap,), np.int32)
    table[live] = np.arange(n_live, dtype=np.int32)

    def gather(x):
        out = jnp.zeros_like(x)
        return out.at[:n_live].set(x[src])

    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    graph = graph.remap(jnp.asarray(table))
    return MutableState(
        buckets.BucketedState(
            LandmarkState(st.landmark_idx,
                          gather(st.representation), gather(st.ratings),
                          graph=NeighborGraph(gather(graph.indices),
                                              gather(graph.weights))),
            jnp.int32(n_live), bst.n_items),
        mst.landmarks,
        jnp.zeros((cap,), bool), jnp.zeros((cap,), bool))


def fold_in_rows(mst: MutableState, rows, bq: int, spec: LandmarkSpec,
                 min_bucket: int = buckets.DEFAULT_MIN_BUCKET,
                 growth: float = buckets.DEFAULT_GROWTH) -> MutableState:
    """Append new users to a mutable state (the fold lane, mutation-aware).

    Same as ``buckets.fold_in_rows`` but the d1 projection goes through the
    *frozen* landmark snapshot — ``st.ratings[landmark_idx]`` may have been
    updated or zeroed by a mutation, and the basis must not drift between
    refreshes. New rows arrive clean (not tombstoned, not dirty: the
    bucketed extend's new-vs-all scan already excludes tombstoned
    candidates because their representation is zeroed... it does NOT — it
    masks by prefix only, so the scan here masks via the tomb bitmap).
    """
    n = len(rows)
    bst, _ = buckets.ensure_capacity(mst.bstate, -(-n // bq) * bq if n else 0,
                                     min_bucket, growth)
    mst = _grow_masks(mst, bst)
    p = bst.n_items
    rows = jnp.asarray(rows)
    for lo in range(0, n, bq):
        chunk = rows[lo:lo + bq]
        m = chunk.shape[0]
        padded = jnp.zeros((bq, p), jnp.float32).at[:m].set(chunk)
        mst = fold_in_mutable(mst, padded, jnp.int32(m), spec)
    return mst


@partial(jax.jit, static_argnames=("spec",))
def fold_in_mutable(mst: MutableState, new_ratings: jax.Array,
                    b_valid: jax.Array, spec: LandmarkSpec) -> MutableState:
    """One bucketed fold-in step with the frozen basis + tombstone masks.

    Delegates to ``buckets.fold_in_bucketed`` (landmarks overridden), then
    re-asserts the tombstone invariant on the touched rows: the bucketed
    extend's candidate masks are prefix-based, so a tombstoned row inside
    the prefix could be picked as a neighbor of a new row (its rep is
    zeroed, but a zero rep still scores — euclidean gives it positive
    similarity). One eviction pass over the appended rows' lists fixes
    that; appended rows whose list lost an entry rescan via the dirty map.
    """
    n0 = mst.bstate.n_valid
    bst = buckets.fold_in_bucketed(
        jax.tree.map(jnp.copy, mst.bstate), new_ratings, b_valid, spec,
        landmarks=mst.landmarks)
    graph = bst.state.graph
    graph, hit = evict_neighbors(graph, mst.tomb)
    cap = bst.capacity
    row_valid = (jnp.arange(cap) < bst.n_valid) & ~mst.tomb
    dirty = mst.dirty | (hit & row_valid)
    return MutableState(
        buckets.BucketedState(
            LandmarkState(bst.state.landmark_idx, bst.state.representation,
                          bst.state.ratings, graph=graph),
            bst.n_valid, bst.n_items),
        mst.landmarks, mst.tomb, dirty)


# ------------------------------------------------------------------- serving
def predict_pairs(mst: MutableState, users: jax.Array, items: jax.Array
                  ) -> jax.Array:
    """Pair predictions with padding AND tombstone masks threaded through."""
    bst = mst.bstate
    return knn.predict_pairs_graph(bst.state.graph, bst.state.ratings,
                                   users, items, n_valid=bst.n_valid,
                                   tomb=mst.tomb)


def recommend_topn(mst: MutableState, users: jax.Array, n: int = 10):
    """Top-N with padding AND tombstone masks threaded through."""
    bst = mst.bstate
    return knn.recommend_topn_graph(bst.state.graph, bst.state.ratings,
                                    users, n=n, n_valid=bst.n_valid,
                                    tomb=mst.tomb, n_items=bst.n_items)
