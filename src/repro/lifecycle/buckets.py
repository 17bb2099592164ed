"""Bucket-padded serving state — one executable per bucket, not per fold-in.

``fold_in`` grows U by b every call, so every request step after it recompiles
(new shapes). This module removes that: arrays are padded to a capacity drawn
from a geometric schedule, the live-row count ``n_valid`` is a *traced* scalar,
and fold-in fills padded slots in place (``extend_neighbor_graph_bucketed``).
The jitted pair/top-N/fold steps therefore compile once per bucket; shapes only
change when the population outgrows its bucket.

Correctness of the padding rests on two invariants, both property-tested
(tests/test_properties.py, tests/test_lifecycle.py):

- rows ``< n_valid`` of the padded graph reference only rows ``< n_valid``;
- rows ``>= n_valid`` hold (index 0, weight 0.0) — inert under Eq. (1).

On top of that, every consumer (``knn.predict_pairs_graph``,
``knn.recommend_topn_graph``) re-zeroes weights of out-of-range neighbor ids
via ``n_valid``, so padded rows cannot leak into predictions or
recommendations even from a corrupted artifact.

The rating matrix is also padded in its columns, with zeros, to a multiple
of ``LANES``; ``n_items`` keeps the real item count. A TPU lays a
``(capacity, P)`` f32 array out column-major when ``P`` is not a multiple
of 128, and a gather of rows from that layout makes XLA transpose the whole
matrix on every read. A zero column is an item nobody rated: it changes no
mean, count or similarity, and the top-N program cuts it off its items.
Writes take rows of ``n_items`` ratings and project them through landmark
rows of ``n_items`` ratings, exactly as before the padding.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.core import knn
from repro.core.graph import extend_neighbor_graph_bucketed
from repro.core.landmark_cf import LandmarkState
from repro.core.similarity import masked_similarity
from repro.core.types import LandmarkSpec, NeighborGraph

DEFAULT_MIN_BUCKET = 256
DEFAULT_GROWTH = 2.0
LANES = 128  # a TPU tile's minor width: the rating matrix's column multiple


def bucket_schedule(max_size: int, min_bucket: int = DEFAULT_MIN_BUCKET,
                    growth: float = DEFAULT_GROWTH) -> List[int]:
    """Geometric capacities ``min_bucket * growth^i`` (rounded up to 8) that
    cover populations up to ``max_size``."""
    assert growth > 1.0, growth
    caps, cap = [], float(min_bucket)
    while True:
        c = -(-int(cap) // 8) * 8
        if not caps or c > caps[-1]:
            caps.append(c)
        if c >= max_size:
            return caps
        cap *= growth


def bucket_capacity(n: int, min_bucket: int = DEFAULT_MIN_BUCKET,
                    growth: float = DEFAULT_GROWTH) -> int:
    """Smallest capacity on the schedule that holds ``n`` rows."""
    return bucket_schedule(n, min_bucket, growth)[-1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BucketedState:
    """A ``LandmarkState`` padded to a bucket capacity + its live-row count.

    ``state`` arrays have leading dimension ``capacity``; rows ``< n_valid``
    are real users, the rest zero filler. ``state.ratings`` has
    ``lane_width(n_items)`` columns, those ``>= n_items`` zero (module
    docstring). The whole thing is a pytree, so the jitted serve/fold steps
    take it directly; ``n_valid`` is a traced leaf — fill level never
    triggers a recompile — and ``n_items`` a static one.
    """

    state: LandmarkState
    n_valid: jax.Array  # () int32
    n_items: int  # real columns of ``state.ratings``

    def tree_flatten(self):
        return (self.state, self.n_valid), (self.n_items,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def capacity(self) -> int:
        return self.state.ratings.shape[0]

    @property
    def k(self) -> int:
        return self.state.graph.k

    def host_ratings(self, lo: int = 0, hi: int = None):
        """Rows ``lo:hi`` of the rating matrix on the host, ``n_items``
        wide: what a refit or a write takes."""
        import numpy as np

        return np.asarray(self.state.ratings)[lo:hi, :self.n_items]


def lane_width(n_items: int) -> int:
    """The bucketed rating matrix's width: ``n_items`` rounded up to LANES."""
    return -(-n_items // LANES) * LANES


def pad_items(rows: jax.Array, width: int) -> jax.Array:
    """Rating rows of ``n_items`` columns, zero-padded to ``width``."""
    return jnp.pad(rows, ((0, 0), (0, width - rows.shape[1])))


def _pad_rows(x: jax.Array, capacity: int, width: int = None) -> jax.Array:
    """``x`` zero-padded to ``capacity`` rows and, given ``width``, that
    many columns, in one allocation."""
    pad = [(0, capacity - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    if width is not None:
        pad[1] = (0, width - x.shape[1])
    assert min(p[1] for p in pad) >= 0, (x.shape, capacity, width)
    # no pad still copies: the padded state feeds the *donating* fold step,
    # which must never alias the caller's source arrays (jnp.pad already
    # allocates fresh buffers when it pads)
    return jnp.pad(x, pad) if any(p[1] for p in pad) else x.copy()


def _pad_state(state: LandmarkState, capacity: int) -> LandmarkState:
    """Zero-pad every user-indexed array to ``capacity`` rows, and the
    ratings to ``lane_width`` columns.

    Zero filler is inert by construction: zero rating rows have mask 0 and
    mean 0, zero graph rows have weight 0, zero columns are unrated items.
    No output leaf aliases an input leaf (``landmark_idx`` is copied
    outright) — donation safety, see :func:`fold_in_bucketed`.
    """
    if state.graph is None:
        raise ValueError("bucketed serving needs a graph-backed state; "
                         "dense-sims states must refit")
    graph = state.graph.to_full() if state.graph.is_compact else state.graph
    return LandmarkState(
        state.landmark_idx.copy(),
        _pad_rows(state.representation, capacity),
        _pad_rows(state.ratings, capacity,
                  lane_width(state.ratings.shape[1])),
        graph=NeighborGraph(_pad_rows(graph.indices, capacity),
                            _pad_rows(graph.weights, capacity)),
    )


def from_state(state: LandmarkState, min_bucket: int = DEFAULT_MIN_BUCKET,
               growth: float = DEFAULT_GROWTH) -> BucketedState:
    """Wrap a fitted state into the smallest bucket that holds it.

    The wrapped state shares no buffers with ``state``: ``fold_in_bucketed``
    donates its input, and an aliased leaf would let the first fold-in
    delete the caller's fitted state under them.
    """
    u = state.ratings.shape[0]
    cap = bucket_capacity(u, min_bucket, growth)
    return BucketedState(_pad_state(state, cap), jnp.int32(u),
                         state.ratings.shape[1])


def ensure_capacity(bstate: BucketedState, incoming: int,
                    min_bucket: int = DEFAULT_MIN_BUCKET,
                    growth: float = DEFAULT_GROWTH) -> Tuple[BucketedState, bool]:
    """Host-side growth check before a fold-in of ``incoming`` rows.

    Returns ``(state, grew)``; when the bucket overflows, arrays are re-padded
    to the next capacity on the schedule (the one deliberate recompile).
    """
    need = int(bstate.n_valid) + incoming
    if need <= bstate.capacity:
        return bstate, False
    cap = bucket_capacity(need, min_bucket, growth)
    return BucketedState(_pad_state(bstate.state, cap), bstate.n_valid,
                         bstate.n_items), True


@partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def fold_in_bucketed(
    bstate: BucketedState,
    new_ratings: jax.Array,  # (bq, n_items) batch; rows >= b_valid are filler
    b_valid: jax.Array,  # () int32 real rows in the batch
    spec: LandmarkSpec,
    landmarks: jax.Array = None,  # (n, P) frozen basis override (mutation path)
) -> BucketedState:
    """Shape-stable ``fold_in``: fill padded slots instead of growing arrays.

    Same math as :func:`repro.core.landmark_cf.fold_in` (d1 through the frozen
    landmarks, new-vs-all scan, back-patch) restricted to the valid prefix;
    see ``extend_neighbor_graph_bucketed`` for the masking. The caller must
    guarantee ``n_valid + bq <= capacity`` (``ensure_capacity``). Compiles
    once per (capacity, bq) pair.

    The incoming ``bstate`` buffers are **donated**: every array is
    capacity-stable (same shape/dtype in and out), so XLA aliases the output
    ratings/rep/graph onto the inputs and the update stops paying a second
    copy of the state in HBM traffic. Callers must treat the passed-in state
    as consumed (every in-repo caller rebinds ``bstate =``). On backends
    without donation (CPU) this is a no-op.

    ``landmarks`` overrides the projection basis. The default re-slices
    ``st.ratings[landmark_idx, :n_items]`` — correct while rating rows are
    immutable, but ``repro.mutation`` updates and zeroes rating rows in
    place, so the mutable path passes its frozen (n, P) snapshot instead
    (the basis must not drift between refreshes).
    """
    st = bstate.state
    n_valid = bstate.n_valid
    bq = new_ratings.shape[0]
    q_valid = (jnp.arange(bq) < b_valid)[:, None]
    new_ratings = jnp.where(q_valid, new_ratings, 0.0)

    if landmarks is None:  # (n, P) frozen: ids < U0
        landmarks = st.ratings[st.landmark_idx, :bstate.n_items]
    new_rep = masked_similarity(new_ratings, landmarks, spec.d1)  # (bq, n)
    new_rep = jnp.where(q_valid, new_rep, 0.0)

    ratings = jax.lax.dynamic_update_slice(
        st.ratings, pad_items(new_ratings, st.ratings.shape[1]), (n_valid, 0))
    rep = jax.lax.dynamic_update_slice(st.representation, new_rep, (n_valid, 0))
    graph = extend_neighbor_graph_bucketed(st.graph, rep, new_rep,
                                           n_valid, b_valid, spec.d2)
    return BucketedState(
        LandmarkState(st.landmark_idx, rep, ratings, graph=graph),
        n_valid + b_valid.astype(jnp.int32), bstate.n_items,
    )


def fold_in_rows(bstate: BucketedState, rows, bq: int, spec: LandmarkSpec,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 growth: float = DEFAULT_GROWTH) -> BucketedState:
    """Host-side fold-in driver: reserve capacity, then fold ``rows`` through
    the jitted step in ``bq``-sized padded batches.

    Capacity is reserved for the *padded* batches (``ceil(len/bq) * bq``): a
    ragged last chunk still writes ``bq`` rows, and the in-place
    ``dynamic_update_slice`` must never clamp against the capacity edge —
    that would overwrite valid rows with filler. This is the one place that
    contract lives; serve, swap-delta refold, and benchmarks all come through
    here.
    """
    n = len(rows)
    bstate, _ = ensure_capacity(bstate, -(-n // bq) * bq if n else 0,
                                min_bucket, growth)
    p = bstate.n_items
    rows = jnp.asarray(rows)
    for lo in range(0, n, bq):
        chunk = rows[lo:lo + bq]
        m = chunk.shape[0]
        padded = jnp.zeros((bq, p), jnp.float32).at[:m].set(chunk)
        bstate = fold_in_bucketed(bstate, padded, jnp.int32(m), spec)
    return bstate


def predict_pairs(bstate: BucketedState, users: jax.Array, items: jax.Array
                  ) -> jax.Array:
    """Serve-path pair predictions with the padded-row mask threaded through."""
    return knn.predict_pairs_graph(bstate.state.graph, bstate.state.ratings,
                                   users, items, n_valid=bstate.n_valid)


def recommend_topn(bstate: BucketedState, users: jax.Array, n: int = 10):
    """Serve-path top-N with the padded-row mask threaded through; the
    padding columns are no item."""
    return knn.recommend_topn_graph(bstate.state.graph, bstate.state.ratings,
                                    users, n=n, n_valid=bstate.n_valid,
                                    n_items=bstate.n_items)


def compact_state(bstate: BucketedState) -> BucketedState:
    """Swap the serving graph to the compact (uint16/bf16) artifact.

    Policy-gated by ``lifecycle.policy.should_compact`` (capacity < 65536);
    predictions consume the compact graph directly, and the next capacity
    growth or bucketed fold-in widens it back (``_pad_state`` /
    ``extend_neighbor_graph_bucketed`` both call ``to_full``).
    """
    st = bstate.state
    if st.graph is None or st.graph.is_compact:
        return bstate
    return BucketedState(
        LandmarkState(st.landmark_idx, st.representation, st.ratings,
                      graph=st.graph.to_compact()),
        bstate.n_valid, bstate.n_items)


# ---------------------------------------------------------------------------
# Sharded serving: the per-shard capacity schedule + host-side fold drivers
# for a ShardedLandmarkState (core.landmark_cf). Each mesh shard carries its
# own capacity-C block and fill count; the geometric schedule now bounds the
# PER-SHARD padded shapes, so one executable per (C, bq) serves the pod.
# ---------------------------------------------------------------------------


def from_state_sharded(state: LandmarkState, mesh, row_axes=("pod", "data"),
                       min_bucket: int = 32, growth: float = DEFAULT_GROWTH
                       ) -> "ShardedLandmarkState":
    """Block-partition a fitted (contiguous) state onto the mesh.

    Dense row g lands on shard ``g // u_per`` at slot ``g % u_per``
    (u_per = ceil(U / S) — the ``streaming_knn_graph_sharded`` linearization),
    each shard block is padded to the smallest per-shard bucket capacity, and
    graph neighbor ids + ``landmark_idx`` are remapped into the sharded id
    space. Capacity is clamped to ``>= k`` so every shard can produce a full
    local candidate list during fold-in.
    """
    import numpy as np

    from repro.core.landmark_cf import ShardedLandmarkState
    from repro.distributed import sharding as shd

    if state.graph is None:
        raise ValueError("sharded serving needs a graph-backed state; "
                         "dense-sims states must refit")
    graph = state.graph.to_full() if state.graph.is_compact else state.graph
    axes = shd.cf_row_axes(mesh, row_axes)
    s = shd.cf_shard_count(mesh, axes)
    u = state.ratings.shape[0]
    u_per = -(-u // s)
    cap = bucket_capacity(max(u_per, graph.k), min_bucket, growth)

    remap = lambda ids: shd.dense_to_sharded_ids(np.asarray(ids), u_per, cap)
    pack = lambda x: shd.pack_row_blocks(np.asarray(x), s, u_per, cap)
    row_sh = shd.cf_row_sharding(mesh, axes)
    rep = jax.device_put(pack(state.representation), row_sh)
    ratings = jax.device_put(pack(state.ratings), row_sh)
    gi = jax.device_put(pack(remap(graph.indices)), row_sh)
    gw = jax.device_put(pack(graph.weights), row_sh)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    idx = jax.device_put(remap(state.landmark_idx).astype(np.int32), repl)
    n_valid = np.clip(u - np.arange(s) * u_per, 0, u_per).astype(np.int32)
    rank = jax.device_put(pack(np.arange(u, dtype=np.int32)),
                          shd.cf_row_sharding(mesh, axes, ndim=1))
    return ShardedLandmarkState(
        LandmarkState(idx, rep, ratings, graph=NeighborGraph(gi, gw)),
        jax.device_put(n_valid, repl), rank, mesh, axes)


def ensure_capacity_sharded(sstate, target: int, incoming: int,
                            min_bucket: int = 32,
                            growth: float = DEFAULT_GROWTH):
    """Growth check before a sharded fold-in of ``incoming`` rows onto shard
    ``target``. When the target block overflows, EVERY shard block is
    re-padded to the next capacity on the schedule and graph ids are remapped
    (one deliberate recompile, same as the single-device schedule). Returns
    ``(sstate, grew)``.

    The overflow decision reads one host scalar (the target shard's fill);
    the repack itself is pure-device — ``repack_row_blocks_device`` pads each
    shard block in place and ``remap_block_ids`` is plain array arithmetic,
    so a pod-sized regrow never round-trips the (S*C, ...) payload through
    host memory.
    """
    import numpy as np

    from repro.core.landmark_cf import ShardedLandmarkState
    from repro.distributed import sharding as shd

    n_valid = np.asarray(sstate.n_valid)
    cap = sstate.capacity
    if int(n_valid[target]) + incoming <= cap:
        return sstate, False
    s = sstate.shard_count
    new_cap = bucket_capacity(int(n_valid[target]) + incoming, min_bucket,
                              growth)
    st = sstate.state
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    repack = lambda x: shd.repack_row_blocks_device(
        x, s, cap, new_cap, sstate.mesh, sstate.axes)
    rep = repack(st.representation)
    ratings = repack(st.ratings)
    gi = repack(shd.remap_block_ids(graph.indices, cap, new_cap))
    gw = repack(graph.weights)
    repl = jax.sharding.NamedSharding(sstate.mesh,
                                      jax.sharding.PartitionSpec())
    idx = jax.device_put(
        shd.remap_block_ids(st.landmark_idx, cap, new_cap), repl)
    rank = repack(sstate.row_rank)
    return ShardedLandmarkState(
        LandmarkState(idx, rep, ratings, graph=NeighborGraph(gi, gw)),
        sstate.n_valid, rank, sstate.mesh, sstate.axes), True


def fold_in_rows_sharded(sstate, rows, bq: int, spec: LandmarkSpec,
                         min_bucket: int = 32,
                         growth: float = DEFAULT_GROWTH):
    """Host-side sharded fold-in driver: pick the least-loaded shard per
    ``bq``-sized batch (ties → lowest shard index, so placement is
    reproducible), reserve capacity, fold through the jitted
    ``core.fold_in_sharded`` step. Returns ``(sstate, shards, slots)`` — the
    (shard, slot) landing position of every row, from which callers derive
    sharded row ids as ``shard * capacity + slot`` (slots are stable across
    capacity regrowth; ids are not).
    """
    import numpy as np

    from repro.core.landmark_cf import fold_in_sharded

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
        sstate, _ = ensure_capacity_sharded(sstate, target, bq, min_bucket,
                                            growth)
        shards[lo:lo + m] = target
        slots[lo:lo + m] = int(fills[target]) + np.arange(m)
        padded = jnp.zeros((bq, p), jnp.float32).at[:m].set(chunk)
        sstate = fold_in_sharded(sstate, padded, jnp.int32(m),
                                 jnp.int32(target), spec)
    return sstate, shards, slots


def predict_pairs_sharded(sstate, users: jax.Array, items: jax.Array
                          ) -> jax.Array:
    """Pair predictions on a ShardedLandmarkState. ``users`` are *sharded*
    row ids (``shard * capacity + slot``); the per-shard fill counts mask
    padded rows exactly like ``n_valid`` does on the single-device path."""
    return knn.predict_pairs_graph(sstate.state.graph, sstate.state.ratings,
                                   users, items, n_valid=sstate.n_valid,
                                   shard_cap=sstate.capacity)


def recommend_topn_sharded(sstate, users: jax.Array, n: int = 10):
    """Top-N on a ShardedLandmarkState (sharded user ids, see above)."""
    return knn.recommend_topn_graph(sstate.state.graph, sstate.state.ratings,
                                    users, n=n, n_valid=sstate.n_valid,
                                    shard_cap=sstate.capacity)
