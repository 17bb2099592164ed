"""Continuous micro-batching request engine over the warm bucketed state.

The wave loops in ``launch/serve.py`` replay *synchronous* traffic: one
batch at a time, reads and fold-ins strictly interleaved. A server faces
concurrent pair/top-N/fold-in requests with tail-latency SLOs. This module
is that server core, kept deliberately host-side and synchronous-testable:

  queue      ``submit()`` admits a request into a bounded deadline heap;
             admission is by *rows* (a top-N request for 32 users costs 32
             rows of queue budget). Overflow sheds — the caller gets
             ``None`` back and the shed counter feeds ``shed_frac``.
  former     ``pump_reads()`` pops requests in deadline order, packs
             same-kind runs up to ``max_batch`` rows, pads to the next
             power-of-two batch shape, and replays ONE jitted call per
             batch. Shapes are drawn from ``EngineConfig.batch_shapes()``,
             so compile count stays bounded at |shapes| x |buckets| per
             request kind — the same executables the lifecycle waves warm.
  write lane writes — fold-ins AND in-place mutations (``"update"`` rating
             replacement, ``"remove"`` GDPR deletion, ``repro.mutation``) —
             go to a separate queue drained by ``pump_folds()`` on its own
             cadence (own thread in threaded mode). A write never runs on
             the read path; it builds the next-generation state off to the
             side (mutations also drain their decremental repairs before
             publishing) and swaps it in with one atomic publish, so an
             in-flight read batch keeps the generation it started with.
  bit-identity
             per-row kNN math is row-independent (reductions run over the
             fixed ``k``/``P`` axes, never over the batch axis), so any
             packing/padding of admitted requests yields bitwise the same
             per-row results as executing each request alone —
             ``verify_sample()`` re-checks exactly that against the live
             generation, and ``tests/test_serving_engine.py`` asserts it
             across random interleavings.

Two backends give the engine one logical-id API on both topologies:
``LocalBackend`` serves a single-device ``BucketedState``;
``ShardedBackend`` serves a ``ShardedLandmarkState`` through the
``serving.router`` shard_map route (never the GSPMD gather), translating
logical ids to ``shard * capacity + slot`` at execution time against the
same published generation tuple.
"""
from __future__ import annotations

import dataclasses
import heapq
import threading
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as obslib
from repro.core import knn
from repro.lifecycle import buckets
from repro.obs.registry import Histogram
from repro.serving.stats import histogram_latency, latency_stats

READ_KINDS = ("pair", "topn")
WRITE_KINDS = ("fold", "update", "remove")


@dataclasses.dataclass
class Request:
    """One admitted request. ``done`` fires after its batch executes."""

    kind: str                       # "pair" | "topn" | "fold" | "update"
    #                                 | "remove"
    users: Optional[np.ndarray]     # logical user ids (reads + mutations)
    items: Optional[np.ndarray]     # item ids (pair reads only)
    rows: Optional[np.ndarray]      # dense rating rows (fold/update writes)
    deadline: float                 # absolute monotonic seconds
    t_submit: float
    seq: int
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    result: object = None           # (b,) preds | (items, scores) | gen
    generation: int = -1            # generation the request executed against
    t_done: float = 0.0
    t_pickup: float = 0.0           # batch-former pickup / write-lane drain
    sampled: bool = False           # selected by the trace sampler
    trace_id: int = 0               # root span id when sampled

    @property
    def n_rows(self) -> int:
        src = self.rows if self.kind == "fold" else self.users
        return int(len(src))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Queueing-model knobs. ``batch_shapes()`` is the compile budget."""

    max_batch: int = 128            # rows per executed read batch
    min_shape: int = 8              # smallest padded batch shape
    queue_cap: int = 1024           # admission bound, in rows
    max_wait_ms: float = 2.0        # batch-fill wait (threaded mode)
    slo_ms: float = 50.0            # default per-request deadline
    fold_queue_cap: int = 64        # fold lane bound, in requests
    fold_bq: int = 32               # fold-in micro-batch quantum
    topn: int = 10

    def batch_shapes(self) -> Tuple[int, ...]:
        shapes = []
        s = max(1, self.min_shape)
        while s < self.max_batch:
            shapes.append(s)
            s *= 2
        shapes.append(self.max_batch)
        return tuple(shapes)

    def pad_shape(self, rows: int) -> int:
        for s in self.batch_shapes():
            if rows <= s:
                return s
        return self.max_batch


class LocalBackend:
    """Single-device executor: logical user id == dense row index.

    ``fold_in_bucketed`` donates its input, so the fold lane clones the
    state before folding — the previous generation's buffers stay alive for
    any read batch still holding them, and the new state swaps in via one
    atomic publish.
    """

    serialize_folds = False  # one device, no collectives: true overlap

    def __init__(self, bst: buckets.BucketedState, spec, *,
                 min_bucket: int = 256, growth: float = 2.0,
                 warm_shapes: Tuple[int, ...] = (), warm_topn: int = 10):
        self.spec = spec
        self.min_bucket = min_bucket
        self.growth = growth
        self.warm_shapes = warm_shapes
        self.warm_topn = warm_topn
        self._pub = (bst, 0)        # (state, generation) — one atomic cell
        self.caps_used = {bst.capacity}  # the serve-path compile budget axis

    def _warm(self, pub) -> None:
        """Compile the read executables for a new bucket capacity BEFORE the
        publish — run on the fold lane, so a capacity regrow never makes a
        read batch pay the recompile (the p99 spike the wave replays dodge
        by warming inside the timed loop)."""
        for s in self.warm_shapes:
            z = np.zeros(s, np.int64)
            jax.block_until_ready(self.predict_pairs(pub, z, z))
            _i, _s = self.recommend_topn(pub, z, self.warm_topn)
            jax.block_until_ready(_s)

    @property
    def generation(self) -> int:
        return self._pub[1]

    @property
    def n_users(self) -> int:
        return int(self._pub[0].n_valid)

    def snapshot(self):
        return self._pub

    def gathers_row_stats(self, kind: str, rows: int) -> bool:
        """Whether a read batch of ``rows`` takes Eq. (1)'s row statistics
        from its gathered rows: top-N always, pairs by
        ``knn.gathers_row_stats`` at the live generation's capacity and k."""
        st = self._pub[0]
        return kind == "topn" or knn.gathers_row_stats(rows, st.k,
                                                        st.capacity)

    def predict_pairs(self, pub, users: np.ndarray, items: np.ndarray):
        bst, _ = pub
        return buckets.predict_pairs(bst, jnp.asarray(users, jnp.int32),
                                     jnp.asarray(items, jnp.int32))

    def recommend_topn(self, pub, users: np.ndarray, n: int):
        bst, _ = pub
        return buckets.recommend_topn(bst, jnp.asarray(users, jnp.int32),
                                      n=n)

    def fold_in(self, rows: np.ndarray, bq: int) -> int:
        bst, gen = self._pub
        clone = jax.tree.map(jnp.copy, bst)   # donation safety
        new = buckets.fold_in_rows(clone, jnp.asarray(rows), bq, self.spec,
                                   min_bucket=self.min_bucket,
                                   growth=self.growth)
        jax.block_until_ready(new.state.ratings)
        if new.capacity not in self.caps_used:
            self._warm((new, gen + 1))
            self.caps_used.add(new.capacity)
        self._pub = (new, gen + 1)
        return gen + 1


class ShardedBackend:
    """Mesh executor: reads go through the shard_map query router, writes
    through ``fold_in_rows_sharded``. Logical ids translate to sharded row
    ids (``shard * capacity + slot``) at execution time against the same
    published (state, tables, generation) tuple, so a capacity regrow
    between publish points can never mix old ids with a new layout.
    """

    # collective programs from two host threads can deadlock the shared
    # per-device rendezvous pool on a single-process mesh — the engine must
    # serialize fold launches with read launches (see RequestEngine)
    serialize_folds = True

    def __init__(self, sstate, id_shard: np.ndarray, id_slot: np.ndarray,
                 spec, *, min_bucket: int = 32, growth: float = 2.0,
                 warm_shapes: Tuple[int, ...] = (), warm_topn: int = 10):
        self.spec = spec
        self.min_bucket = min_bucket
        self.growth = growth
        self.warm_shapes = warm_shapes
        self.warm_topn = warm_topn
        self._pub = (sstate, np.asarray(id_shard), np.asarray(id_slot), 0)
        self.caps_used = {sstate.capacity}

    def _warm(self, pub) -> None:
        """Pre-compile the routed read executables at a new shard capacity on
        the fold lane, so the publish never hands reads a cold executable."""
        for s in self.warm_shapes:
            z = np.zeros(s, np.int64)
            jax.block_until_ready(self.predict_pairs(pub, z, z))
            _i, _s = self.recommend_topn(pub, z, self.warm_topn)
            jax.block_until_ready(_s)

    @property
    def generation(self) -> int:
        return self._pub[3]

    @property
    def n_users(self) -> int:
        return len(self._pub[1])

    def snapshot(self):
        return self._pub

    @staticmethod
    def gathers_row_stats(kind: str, rows: int) -> bool:
        """The router takes row statistics from each shard's whole block."""
        return False

    @staticmethod
    def _sharded_ids(pub, users: np.ndarray) -> jnp.ndarray:
        sstate, id_shard, id_slot, _ = pub
        sids = id_shard[users] * sstate.capacity + id_slot[users]
        return jnp.asarray(sids, jnp.int32)

    def predict_pairs(self, pub, users: np.ndarray, items: np.ndarray):
        from repro.serving.router import predict_pairs_routed
        return predict_pairs_routed(pub[0], self._sharded_ids(pub, users),
                                    jnp.asarray(items, jnp.int32))

    def recommend_topn(self, pub, users: np.ndarray, n: int):
        from repro.serving.router import recommend_topn_routed
        return recommend_topn_routed(pub[0], self._sharded_ids(pub, users),
                                     n=n)

    def fold_in(self, rows: np.ndarray, bq: int) -> int:
        sstate, id_shard, id_slot, gen = self._pub
        new, shards, slots = buckets.fold_in_rows_sharded(
            sstate, jnp.asarray(rows), bq, self.spec,
            min_bucket=self.min_bucket, growth=self.growth)
        jax.block_until_ready(new.state.ratings)
        pub = (new,
               np.concatenate([id_shard, np.asarray(shards)]),
               np.concatenate([id_slot, np.asarray(slots)]),
               gen + 1)
        if new.capacity not in self.caps_used:
            self._warm(pub)
            self.caps_used.add(new.capacity)
        self._pub = pub
        return gen + 1


def _mutation_shape(m: int, lo: int = 8) -> int:
    """Power-of-two mutation batch shapes (floor ``lo``) — compile count per
    capacity stays logarithmic in the largest batch, like the read former."""
    s = max(1, lo)
    while s < m:
        s *= 2
    return s


def _pad_mutation(row_ids: np.ndarray, rows: Optional[np.ndarray]):
    """Pad a mutation of the state rows ``row_ids`` to its power-of-two
    shape (-1 pads) and move it to the device: ``(row ids, rating rows or
    None, count)``."""
    m = len(row_ids)
    shape = _mutation_shape(m)
    pid = np.full(shape, -1, np.int64)
    pid[:m] = row_ids
    if rows is None:
        return jnp.asarray(pid, jnp.int32), None, jnp.int32(m)
    prows = np.zeros((shape, rows.shape[1]), np.float32)
    prows[:m] = rows
    return (jnp.asarray(pid, jnp.int32),
            jnp.asarray(prows, jnp.float32), jnp.int32(m))


class MutableLocalBackend(LocalBackend):
    """:class:`LocalBackend` with the write path open.

    The published cell holds a ``mutation.MutableState`` (frozen landmark
    basis + tombstone/dirty bitmaps) instead of a bare ``BucketedState``.
    Reads thread the tombstone mask (a deleted user is invisible the moment
    the remove publishes — no repair or compaction on the read path);
    ``"update"`` / ``"remove"`` requests ride the write lane, drain their
    decremental repairs, and publish the next generation exactly like a
    fold. ``refresh()`` is the swap boundary: it compacts tombstones out
    physically and returns the old→new row-id table for the caller's id
    universe.
    """

    def __init__(self, bst: buckets.BucketedState, spec, *,
                 repair_bq: int = 64, **kw):
        super().__init__(bst, spec, **kw)
        from repro import mutation
        self._mut = mutation
        self.repair_bq = repair_bq
        self.repaired_rows = 0
        self._pub = (mutation.from_bucketed(bst), 0)

    @property
    def tombstone_frac(self) -> float:
        return self._pub[0].tombstone_frac()

    def tomb(self) -> np.ndarray:
        """Host view of the live generation's tombstone bitmap."""
        return np.asarray(self._pub[0].tomb)

    def predict_pairs(self, pub, users: np.ndarray, items: np.ndarray):
        mst, _ = pub
        return self._mut.predict_pairs(mst, jnp.asarray(users, jnp.int32),
                                       jnp.asarray(items, jnp.int32))

    def recommend_topn(self, pub, users: np.ndarray, n: int):
        mst, _ = pub
        return self._mut.recommend_topn(mst, jnp.asarray(users, jnp.int32),
                                        n=n)

    def fold_in(self, rows: np.ndarray, bq: int) -> int:
        mst, gen = self._pub
        with obslib.span("write.prepare", cat="write"):
            rows = jnp.asarray(rows)
        with obslib.span("write.mutate", cat="write"):
            new = self._mut.fold_in_rows(mst, rows, bq, self.spec,
                                         min_bucket=self.min_bucket,
                                         growth=self.growth)
            jax.block_until_ready(new.bstate.state.ratings)
        if new.capacity not in self.caps_used:
            self._warm((new, gen + 1))
            self.caps_used.add(new.capacity)
        with obslib.span("write.publish", cat="write"):
            self._pub = (new, gen + 1)
        return gen + 1

    def _pad_mutation(self, ids: np.ndarray, rows: Optional[np.ndarray]):
        with obslib.span("write.prepare", cat="write"):
            return _pad_mutation(ids, rows)

    def _publish_mutation(self, mutate) -> int:
        """Apply ``mutate`` to the live state, drain its repairs, publish:
        the ``write.mutate``, ``repair.drain`` and ``write.publish``
        phases."""
        mst, gen = self._pub
        with obslib.span("write.mutate", cat="write"):
            mst = mutate(mst)
            jax.block_until_ready(mst.bstate.state.ratings)
            self.repaired_rows += mst.dirty_count()
        mst = self._mut.drain_repairs(mst, self.spec, self.repair_bq)
        with obslib.span("write.publish", cat="write"):
            jax.block_until_ready(mst.bstate.state.ratings)
            self._pub = (mst, gen + 1)
        return gen + 1

    def apply_update(self, ids: np.ndarray, rows: np.ndarray) -> int:
        pid, prows, m = self._pad_mutation(np.asarray(ids),
                                           np.asarray(rows))
        return self._publish_mutation(
            lambda mst: self._mut.update_ratings(mst, pid, prows, m,
                                                 self.spec))

    def apply_remove(self, ids: np.ndarray) -> int:
        pid, _, m = self._pad_mutation(np.asarray(ids), None)
        return self._publish_mutation(
            lambda mst: self._mut.remove_users(mst, pid, m))

    def refresh(self) -> Tuple[int, np.ndarray]:
        """Refresh-boundary compaction: drain outstanding repairs, slide the
        tombstoned rows out physically, publish. Returns ``(generation,
        table)`` where ``table[old_id]`` is the surviving row's new id or
        ``-1`` — the caller remaps its id universe once per swap; between
        swaps ids are stable and deletions purely logical."""
        mst, gen = self._pub
        mst = self._mut.drain_repairs(mst, self.spec, self.repair_bq)
        tomb = np.asarray(mst.tomb)
        nv = int(mst.n_valid)
        live = ~tomb[:nv]
        table = np.full(len(tomb), -1, np.int64)
        table[:nv][live] = np.arange(int(live.sum()))
        mst = self._mut.compact_tombstones(mst)
        jax.block_until_ready(mst.bstate.state.ratings)
        self._pub = (mst, gen + 1)
        return gen + 1, table


class MutableShardedBackend(ShardedBackend):
    """:class:`ShardedBackend` with the write path open — the published cell
    holds a ``mutation.MutableStateSharded``; reads go through the routed
    request path with the replicated tombstone mask; mutations translate
    logical ids to sharded row ids against the same published tables, apply
    owner-shard-local, and drain the all-gather repair merge before
    publishing. ``refresh()`` compacts per shard (rows never change owner)
    and renumbers the logical→(shard, slot) tables in place."""

    def __init__(self, sstate, id_shard: np.ndarray, id_slot: np.ndarray,
                 spec, *, repair_bq: int = 64, **kw):
        super().__init__(sstate, id_shard, id_slot, spec, **kw)
        from repro import mutation
        self._mut = mutation
        self.repair_bq = repair_bq
        self.repaired_rows = 0
        self._pub = (mutation.from_sharded(sstate),
                     np.asarray(id_shard), np.asarray(id_slot), 0)

    @property
    def tombstone_frac(self) -> float:
        return self._pub[0].tombstone_frac()

    def tomb(self) -> np.ndarray:
        """Host tombstone bitmap indexed by *logical* id (translated)."""
        msst, id_shard, id_slot, _ = self._pub
        t = np.asarray(msst.tomb)
        return t[id_shard * msst.capacity + id_slot]

    @staticmethod
    def _sharded_ids(pub, users: np.ndarray) -> jnp.ndarray:
        msst, id_shard, id_slot, _ = pub
        sids = id_shard[users] * msst.capacity + id_slot[users]
        return jnp.asarray(sids, jnp.int32)

    def predict_pairs(self, pub, users: np.ndarray, items: np.ndarray):
        from repro.serving.router import predict_pairs_routed
        msst = pub[0]
        return predict_pairs_routed(msst.sstate,
                                    self._sharded_ids(pub, users),
                                    jnp.asarray(items, jnp.int32),
                                    tomb=msst.tomb)

    def recommend_topn(self, pub, users: np.ndarray, n: int):
        from repro.serving.router import recommend_topn_routed
        msst = pub[0]
        return recommend_topn_routed(msst.sstate,
                                     self._sharded_ids(pub, users),
                                     n=n, tomb=msst.tomb)

    def fold_in(self, rows: np.ndarray, bq: int) -> int:
        msst, id_shard, id_slot, gen = self._pub
        with obslib.span("write.prepare", cat="write"):
            rows = jnp.asarray(rows)
        with obslib.span("write.mutate", cat="write"):
            new, shards, slots = self._mut.fold_in_rows_sharded(
                msst, rows, bq, self.spec,
                min_bucket=self.min_bucket, growth=self.growth)
            jax.block_until_ready(new.sstate.state.ratings)
        pub = (new,
               np.concatenate([id_shard, np.asarray(shards)]),
               np.concatenate([id_slot, np.asarray(slots)]),
               gen + 1)
        if new.capacity not in self.caps_used:
            self._warm(pub)
            self.caps_used.add(new.capacity)
        with obslib.span("write.publish", cat="write"):
            self._pub = pub
        return gen + 1

    def _publish_mutation(self, mutate) -> int:
        """Sharded twin of ``MutableLocalBackend._publish_mutation``, with
        the same phases."""
        msst, id_shard, id_slot, gen = self._pub
        with obslib.span("write.mutate", cat="write"):
            msst = mutate(msst)
            jax.block_until_ready(msst.sstate.state.ratings)
            self.repaired_rows += msst.dirty_count()
        msst = self._mut.drain_repairs_sharded(msst, self.spec,
                                               self.repair_bq)
        with obslib.span("write.publish", cat="write"):
            jax.block_until_ready(msst.sstate.state.ratings)
            self._pub = (msst, id_shard, id_slot, gen + 1)
        return gen + 1

    def _mutation_batch(self, ids: np.ndarray, rows: Optional[np.ndarray]):
        with obslib.span("write.prepare", cat="write"):
            sids = np.asarray(self._sharded_ids(self._pub, ids), np.int64)
            return _pad_mutation(sids, rows)

    def apply_update(self, ids: np.ndarray, rows: np.ndarray) -> int:
        pid, prows, m = self._mutation_batch(np.asarray(ids),
                                             np.asarray(rows))
        return self._publish_mutation(
            lambda msst: self._mut.update_ratings_sharded(msst, pid, prows,
                                                          m, self.spec))

    def apply_remove(self, ids: np.ndarray) -> int:
        pid, _, m = self._mutation_batch(np.asarray(ids), None)
        return self._publish_mutation(
            lambda msst: self._mut.remove_users_sharded(msst, pid, m))

    def refresh(self) -> Tuple[int, np.ndarray]:
        """Per-shard compaction at the swap boundary. Returns
        ``(generation, table)`` over *logical* ids (-1 == removed); the
        backend's own logical→(shard, slot) tables are renumbered in place,
        so surviving logical ids keep working without caller involvement —
        the table is for callers tracking removed ids."""
        msst, id_shard, id_slot, gen = self._pub
        msst = self._mut.drain_repairs_sharded(msst, self.spec,
                                               self.repair_bq)
        c = msst.capacity
        tomb = np.asarray(msst.tomb)
        sid = id_shard * c + id_slot
        # new slot of a surviving row = live slots below it in its shard
        live = ~tomb
        below = np.zeros_like(tomb, np.int64)
        for sh in range(msst.shard_count):
            blk = live[sh * c:(sh + 1) * c]
            below[sh * c:(sh + 1) * c] = np.cumsum(blk) - blk
        dead = tomb[sid]
        new_slot = np.where(dead, 0, below[sid])
        msst = self._mut.compact_tombstones_sharded(msst)
        jax.block_until_ready(msst.sstate.state.ratings)
        table = np.where(dead, -1, np.arange(len(sid), dtype=np.int64))
        self._pub = (msst, np.where(dead, 0, id_shard).astype(id_shard.dtype),
                     new_slot.astype(id_slot.dtype), gen + 1)
        return gen + 1, table


class RequestEngine:
    """Deadline-heap admission + continuous micro-batching + async folds.

    The core is synchronous and single-threaded-testable: ``submit()`` then
    ``pump_reads()`` / ``pump_folds()``. ``start()`` wraps the two pumps in
    their own threads for open-loop load generation; folds then drain on a
    cadence that never touches the read thread.

    ``exec_lock`` serializes device-program *launches*. Read batches always
    hold it (uncontended on the happy path — microseconds). Folds take it
    only when the backend sets ``serialize_folds`` (the sharded backend: on
    a single-process host mesh, two concurrently-launched collective
    programs can each park a subset of the shared per-device threads at
    their rendezvous and starve the other program's remaining ranks — a
    permanent deadlock, not a slowdown). Sidecar device work that runs
    beside a live engine (e.g. retrieval health probes) must hold the same
    lock for the same reason.
    """

    def __init__(self, backend, config: EngineConfig = EngineConfig(),
                 clock: Callable[[], float] = time.monotonic,
                 obs: Optional["obslib.Observability"] = None):
        self.backend = backend
        self.config = config
        self.clock = clock
        # obs is optional; the tracer reference is always valid (the
        # DISABLED singleton's inert tracer when off) so hot-path guards
        # are a single ``.active`` attribute read, never a None check +
        # attribute chain.
        self.obs = obs
        self._tracer = obs.tracer if obs is not None else obslib.DISABLED.tracer
        self.exec_lock = threading.Lock()
        self._lock = threading.Lock()
        self._read_cond = threading.Condition(self._lock)
        self._fold_cond = threading.Condition(self._lock)
        self._heap: List[Tuple[float, int, Request]] = []
        self._folds: List[Request] = []
        self._queued_rows = 0
        self._seq = 0
        self._threads: List[threading.Thread] = []
        self._running = False
        # stats
        self.submitted = {k: 0 for k in READ_KINDS + WRITE_KINDS}
        self.shed = {k: 0 for k in READ_KINDS + WRITE_KINDS}
        self.completed = {k: 0 for k in READ_KINDS + WRITE_KINDS}
        # bounded log-bucketed histograms (ms) — fixed memory regardless of
        # how long the server runs, quantiles within one bucket width
        self.latencies = {k: Histogram() for k in READ_KINDS + WRITE_KINDS}
        self.launches: dict = {}        # (kind, pad_shape) -> launch count
        self.batches = 0
        self.exec_rows = 0
        self.pad_rows = 0
        self.nonfinite = 0
        self.folded_rows = 0
        self.mutated_rows = 0
        self._verify_ring: List[Tuple[Request, object]] = []
        self._verify_cap = 64

    # ------------------------------------------------------------- admission
    def submit(self, kind: str, *, users=None, items=None, rows=None,
               deadline_ms: Optional[float] = None) -> Optional[Request]:
        """Admit one request; returns it, or ``None`` when shed."""
        now = self.clock()
        slo = self.config.slo_ms if deadline_ms is None else deadline_ms
        if kind in READ_KINDS:
            users = np.asarray(users, np.int64)
            if kind == "pair":
                items = np.asarray(items, np.int64)
            req = Request(kind, users, items, None, now + slo / 1e3, now, 0)
            if req.n_rows > self.config.max_batch:
                raise ValueError(
                    f"request of {req.n_rows} rows exceeds max_batch="
                    f"{self.config.max_batch}; split it client-side")
            with self._lock:
                if self._queued_rows + req.n_rows > self.config.queue_cap:
                    self.shed[kind] += 1
                    return None
                req.seq = self._seq = self._seq + 1
                self._queued_rows += req.n_rows
                self.submitted[kind] += 1
                heapq.heappush(self._heap, (req.deadline, req.seq, req))
                self._read_cond.notify()
            tr = self._tracer
            if tr.active and tr.should_sample():
                req.sampled = True
                req.trace_id = tr.new_id()
            return req
        if kind in WRITE_KINDS:
            if kind != "fold" and not hasattr(self.backend, "apply_update"):
                raise ValueError(
                    f"kind {kind!r} needs a mutable backend "
                    "(MutableLocalBackend / MutableShardedBackend)")
            if kind == "fold":
                req = Request(kind, None, None, np.asarray(rows),
                              now + slo / 1e3, now, 0)
            elif kind == "update":
                req = Request(kind, np.asarray(users, np.int64), None,
                              np.asarray(rows), now + slo / 1e3, now, 0)
            else:  # remove
                req = Request(kind, np.asarray(users, np.int64), None, None,
                              now + slo / 1e3, now, 0)
            with self._lock:
                if len(self._folds) >= self.config.fold_queue_cap:
                    self.shed[kind] += 1
                    return None
                req.seq = self._seq = self._seq + 1
                self.submitted[kind] += 1
                self._folds.append(req)
                self._fold_cond.notify()
            tr = self._tracer
            if tr.active and tr.should_sample():
                req.sampled = True
                req.trace_id = tr.new_id()
            return req
        raise ValueError(f"unknown request kind {kind!r}")

    # ---------------------------------------------------------- batch former
    def _form_batch(self) -> List[Request]:
        """Take the earliest-deadline request's kind, then fill with that
        kind's requests in deadline order up to ``max_batch`` rows, skipping
        over other-kind entries (they keep their heap position and form the
        next batch — per-kind deadline order is preserved, and the other
        kind cannot starve because its earliest deadline picks the next
        batch's kind). Caller holds the lock."""
        if not self._heap:
            return []
        kind = self._heap[0][2].kind
        batch, deferred, rows = [], [], 0
        while self._heap:
            entry = heapq.heappop(self._heap)
            nxt = entry[2]
            if nxt.kind != kind:
                deferred.append(entry)
                continue
            if batch and rows + nxt.n_rows > self.config.max_batch:
                deferred.append(entry)
                break
            self._queued_rows -= nxt.n_rows
            batch.append(nxt)
            rows += nxt.n_rows
        for entry in deferred:
            heapq.heappush(self._heap, entry)
        return batch

    def _execute(self, batch: List[Request], form=None) -> None:
        """Run one batch. ``form`` is the open ``read.form`` phase when
        tracing: the batch's phases then tile the read thread's time from
        pickup to the last ``done.set()``, and ``execute[kind]`` (launch to
        answers on the host) holds ``execute.dispatch``, ``.device`` and
        ``.fetch``."""
        kind = batch[0].kind
        rows = sum(r.n_rows for r in batch)
        shape = self.config.pad_shape(rows)
        users = np.zeros(shape, np.int64)
        items = np.zeros(shape, np.int64)
        off = 0
        for r in batch:
            users[off:off + r.n_rows] = r.users
            if kind == "pair":
                items[off:off + r.n_rows] = r.items
            off += r.n_rows
        tr = self._tracer
        traced = form is not None
        if traced:
            evs = [form.end()]
            ph = tr.phase("exec_wait", "engine", self.clock, {"kind": kind})
        with self.exec_lock:
            if traced:
                evs.append(ph.end())
                ex = tr.phase(f"execute[{kind}]", "engine", self.clock,
                              {"rows": rows, "shape": shape})
                ph = tr.phase("execute.dispatch", "engine", self.clock)
            pub = self.backend.snapshot()
            if kind == "pair":
                out = self.backend.predict_pairs(pub, users, items)
            else:
                out = self.backend.recommend_topn(pub, users,
                                                  self.config.topn)
            if traced:
                evs.append(ph.end())
                ph = tr.phase("execute.device", "engine", self.clock)
            jax.block_until_ready(out)
            if traced:
                evs.append(ph.end())
                ph = tr.phase("execute.fetch", "engine", self.clock)
            if kind == "pair":
                out = np.asarray(out)
                self.nonfinite += int((~np.isfinite(out[:rows])).sum())
            else:
                out = (np.asarray(out[0]), np.asarray(out[1]))
        gen = pub[-1]   # both backends publish (..., generation)
        if traced:
            bid = batch[0].seq
            evs.append(ph.end())
            ex.args.update(gen=gen, batch=bid)
            evs.append(ex.end())
            now = ex.t1
            ph = tr.phase("read.scatter", "engine", self.clock)
        else:
            now = self.clock()
        off = 0
        for r in batch:
            if kind == "pair":
                r.result = out[off:off + r.n_rows]
            else:
                r.result = (out[0][off:off + r.n_rows],
                            out[1][off:off + r.n_rows])
            off += r.n_rows
            r.generation = gen
            r.t_done = now
            self.completed[kind] += 1
            self.latencies[kind].record((now - r.t_submit) * 1e3)
            r.done.set()
            if len(self._verify_ring) < self._verify_cap:
                self._verify_ring.append((r, r.result))
        self.batches += 1
        self.exec_rows += rows
        self.pad_rows += shape - rows
        key = (kind, shape)
        self.launches[key] = self.launches.get(key, 0) + 1
        if traced:
            evs.append(ph.end())
            tr.complete_many(evs)
            recs = [(kind, r.t_submit, r.t_pickup, now, r.trace_id,
                     r.n_rows, gen, bid) for r in batch if r.sampled]
            if recs:
                tr.complete_requests(recs, child="exec")

    def pump_reads(self, max_batches: Optional[int] = None) -> int:
        """Drain queued reads now; returns the number of batches executed."""
        n = 0
        tr = self._tracer
        while max_batches is None or n < max_batches:
            form = None
            with self._lock:
                if tr.active and self._heap:
                    form = tr.phase("read.form", "engine", self.clock,
                                    {"queued": self._queued_rows})
                batch = self._form_batch()
            if not batch:
                break
            tp = self.clock()
            for r in batch:
                r.t_pickup = tp
            self._execute(batch, form)
            n += 1
        return n

    # ------------------------------------------------------------ write lane
    def _apply_write(self, req: Request) -> int:
        if req.kind == "fold":
            return self.backend.fold_in(req.rows, self.config.fold_bq)
        if req.kind == "update":
            return self.backend.apply_update(req.users, req.rows)
        return self.backend.apply_remove(req.users)

    def pump_folds(self, max_folds: Optional[int] = None) -> int:
        """Drain queued writes — fold-ins, updates, removals — now (never
        called from the read path). With tracing on, each write runs with
        the engine's obs current on this thread (``obslib.scoped``), so the
        backend's ``write.*`` and ``repair.*`` phases record here without
        a process-wide ``install()``."""
        n = 0
        tr = self._tracer
        serialize = getattr(self.backend, "serialize_folds", False)
        while max_folds is None or n < max_folds:
            with self._lock:
                if not self._folds:
                    break
                req = self._folds.pop(0)
            if tr.active:
                gen, now, evs = self._apply_traced(req, serialize)
            else:
                if serialize:
                    with self.exec_lock:
                        gen = self._apply_write(req)
                else:
                    gen = self._apply_write(req)
                now = self.clock()
                evs = None
            req.result = gen
            req.generation = gen
            req.t_done = now
            with self._lock:
                self.completed[req.kind] += 1
                self.latencies[req.kind].record((now - req.t_submit) * 1e3)
                if req.kind == "fold":
                    self.folded_rows += len(req.rows)
                else:
                    self.mutated_rows += len(req.users)
                self._verify_ring.clear()   # prior generation retired
            req.done.set()
            if evs is not None:
                tr.complete_many(evs)
                if req.sampled:
                    tr.complete_requests(
                        [(req.kind, req.t_submit, req.t_pickup, now,
                          req.trace_id, req.n_rows, gen, None)],
                        child="apply")
            n += 1
        return n

    def _apply_traced(self, req: Request, serialize: bool):
        """One write as phases: ``exec_wait`` (only where folds serialize
        with reads) then ``apply[kind]``, pickup to publish. Returns
        ``(generation, t_done, span records)``."""
        tr = self._tracer
        req.t_pickup = self.clock()
        evs = []
        with obslib.scoped(self.obs):
            if serialize:
                ph = tr.phase("exec_wait", "engine", self.clock,
                              {"kind": req.kind})
                self.exec_lock.acquire()
                evs.append(ph.end())
            try:
                ph = tr.phase(f"apply[{req.kind}]", "write", self.clock,
                              {"rows": req.n_rows})
                gen = self._apply_write(req)
                ph.args["gen"] = gen
                evs.append(ph.end())
            finally:
                if serialize:
                    self.exec_lock.release()
        now = ph.t1
        return gen, now, evs

    # -------------------------------------------------------------- threaded
    def start(self) -> None:
        self._running = True

        def read_loop():
            tr = self._tracer
            while True:
                traced = tr.active
                evs = []
                with self._lock:
                    ph = (tr.phase("read.idle", "engine", self.clock)
                          if traced and self._running and not self._heap
                          else None)
                    while self._running and not self._heap:
                        self._read_cond.wait(timeout=0.05)
                    if ph is not None:
                        evs.append(ph.end())
                    first = self._heap[0][2] if self._heap else None
                if first is None:   # stopped with nothing queued
                    if evs:
                        tr.complete_many(evs)
                    return
                # brief fill wait: let the batch accumulate, bounded by
                # max_wait and by the earliest deadline
                ph = None
                if traced:
                    ph = tr.phase("read.fill", "engine", self.clock)
                t = self.clock()
                deadline = t + min(self.config.max_wait_ms / 1e3,
                                   max(0.0, first.deadline - t))
                while (self.clock() < deadline
                       and self._queued_rows < self.config.max_batch):
                    time.sleep(0.0005)
                if ph is not None:
                    evs.append(ph.end())
                    tr.complete_many(evs)
                self.pump_reads(max_batches=1)

        def fold_loop():
            while True:
                with self._lock:
                    while self._running and not self._folds:
                        self._fold_cond.wait(timeout=0.05)
                    if not self._running and not self._folds:
                        return
                self.pump_folds(max_folds=1)

        for fn, name in ((read_loop, "engine-reads"),
                         (fold_loop, "engine-folds")):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        with self._lock:
            self._running = False
            self._read_cond.notify_all()
            self._fold_cond.notify_all()
        for t in self._threads:
            t.join(timeout=30.0)
        self._threads = []

    # ----------------------------------------------------------------- stats
    def stats(self) -> dict:
        offered = sum(self.submitted.values()) + sum(self.shed.values())
        reads = sum(self.completed[k] for k in READ_KINDS)
        read_h = Histogram()
        for k in READ_KINDS:
            read_h.merge(self.latencies[k])
        with self._lock:
            queue_rows = self._queued_rows
            write_queue = len(self._folds)
        return {
            "offered": offered,
            "submitted": dict(self.submitted),
            "completed": dict(self.completed),
            "shed": dict(self.shed),
            "shed_frac": (sum(self.shed.values()) / offered
                          if offered else 0.0),
            # per-kind shed fractions: write-lane pressure is visible
            # separately from read pressure instead of one aggregate
            "shed_frac_by_kind": {
                k: (self.shed[k] / (self.submitted[k] + self.shed[k])
                    if self.submitted[k] + self.shed[k] else 0.0)
                for k in READ_KINDS + WRITE_KINDS},
            "queue_rows": queue_rows,
            "write_queue": write_queue,
            "read_latency": histogram_latency(read_h),
            "fold_latency": histogram_latency(self.latencies["fold"]),
            "batches": self.batches,
            "mean_batch_rows": (self.exec_rows / self.batches
                                if self.batches else 0.0),
            "pad_frac": (self.pad_rows /
                         max(1, self.pad_rows + self.exec_rows)),
            "nonfinite": self.nonfinite,
            "folded_rows": self.folded_rows,
            "mutated_rows": self.mutated_rows,
            "tombstone_frac": getattr(self.backend, "tombstone_frac", 0.0),
            "repaired_rows": getattr(self.backend, "repaired_rows", 0),
            "generation": self.backend.generation,
            "reads_completed": reads,
        }

    def publish_metrics(self) -> None:
        """Copy the engine's hot-path stats into the obs registry — called
        at snapshot points (periodic, end-of-run), never per request, so
        the registry adds zero cost to the serve path. Idempotent: counters
        and histograms are published as absolute copies (``set`` /
        ``publish_histogram``), never re-accumulated."""
        o = self.obs
        if o is None or not o.enabled:
            return
        reg = o.registry
        for k in READ_KINDS + WRITE_KINDS:
            reg.counter(f"engine.submitted.{k}").set(self.submitted[k])
            reg.counter(f"engine.shed.{k}").set(self.shed[k])
            reg.counter(f"engine.completed.{k}").set(self.completed[k])
            reg.publish_histogram(f"engine.latency_ms.{k}",
                                  self.latencies[k])
        gathered = {k: 0 for k in READ_KINDS}
        for (kind, shape), c in list(self.launches.items()):
            reg.counter(f"exec.engine.{kind}.b{shape}.launches").set(c)
            if self.backend.gathers_row_stats(kind, shape):
                gathered[kind] += c
        for kind, c in gathered.items():
            reg.counter(f"exec.engine.{kind}.gathered_stats").set(c)
        reg.counter("engine.batches").set(self.batches)
        reg.counter("engine.exec_rows").set(self.exec_rows)
        reg.counter("engine.pad_rows").set(self.pad_rows)
        reg.counter("engine.nonfinite").set(self.nonfinite)
        reg.counter("engine.folded_rows").set(self.folded_rows)
        reg.counter("engine.mutated_rows").set(self.mutated_rows)
        with self._lock:
            queue_rows = self._queued_rows
            write_queue = len(self._folds)
        reg.gauge("engine.queue_rows").set(float(queue_rows))
        reg.gauge("engine.write_queue").set(float(write_queue))
        reg.gauge("engine.row_occupancy").set(
            self.exec_rows / max(1, self.exec_rows + self.pad_rows))
        reg.gauge("engine.generation").set(float(self.backend.generation))
        reg.gauge("engine.tombstone_frac").set(
            float(getattr(self.backend, "tombstone_frac", 0.0)))

    def verify_sample(self, limit: int = 16) -> Tuple[int, int]:
        """Re-run recent completed reads SOLO against their generation and
        count bitwise mismatches. Only requests still on the live generation
        are checked (folds clear the ring), so the comparison is exact.
        """
        pub = self.backend.snapshot()
        gen = pub[-1]
        checked = bad = 0
        with self._lock:
            ring = list(self._verify_ring)[:limit]
        for req, got in ring:
            if req.generation != gen:
                continue
            checked += 1
            shape = self.config.pad_shape(req.n_rows)
            users = np.zeros(shape, np.int64)
            users[:req.n_rows] = req.users
            if req.kind == "pair":
                items = np.zeros(shape, np.int64)
                items[:req.n_rows] = req.items
                ref = np.asarray(self.backend.predict_pairs(
                    pub, users, items))[:req.n_rows]
                ok = np.array_equal(ref, got)
            else:
                ti, ts = self.backend.recommend_topn(pub, users,
                                                     self.config.topn)
                ok = (np.array_equal(np.asarray(ti)[:req.n_rows], got[0])
                      and np.array_equal(np.asarray(ts)[:req.n_rows],
                                         got[1]))
            bad += 0 if ok else 1
        return checked, bad
