"""Request-path serving engine: continuous micro-batching, admission
control, the async fold lane, and the shard_map query router — the
micro-batched results must be bit-identical to per-request execution.

Single-device tests run anywhere; the router/sharded-engine tests need the
forced 8-device host platform (same idiom as test_sharded_serving.py).
"""
import os
import threading
import time

import pytest

# These tests need >1 device; spawn-style env var must be set before jax init.
if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import LandmarkSpec, RatingMatrix, knn  # noqa: E402
from repro.core.landmark_cf import fit  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.lifecycle import buckets  # noqa: E402
from repro.obs import Observability  # noqa: E402
from repro.serving import (  # noqa: E402
    EngineConfig,
    LocalBackend,
    MutableLocalBackend,
    RequestEngine,
    ShardedBackend,
    latency_stats,
    materialization_check,
)

SPEC = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
U, P = 64, 24
CFG = EngineConfig(max_batch=16, min_shape=4, queue_cap=64, max_wait_ms=1.0,
                   slo_ms=250.0, fold_bq=8, topn=5)


def _ratings(u, p, density=0.35, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u, p)).astype(np.float32)
    r *= rng.random((u, p)) < density
    return r


@pytest.fixture(scope="module")
def state():
    r = _ratings(U, P, seed=3)
    return fit(jax.random.PRNGKey(0), RatingMatrix(jnp.asarray(r), U, P), SPEC)


def _local_backend(state):
    return LocalBackend(buckets.from_state(state, min_bucket=U), SPEC,
                        min_bucket=U)


def _solo(backend, pub, req, cfg):
    """Replay one request alone, padded exactly as the engine pads it."""
    m = req.n_rows
    u = np.zeros(cfg.pad_shape(m), np.int64)
    u[:m] = req.users
    if req.kind == "pair":
        it = np.zeros_like(u)
        it[:m] = req.items
        return np.asarray(backend.predict_pairs(pub, u, it))[:m]
    ti, ts = backend.recommend_topn(pub, u, cfg.topn)
    return np.asarray(ti)[:m], np.asarray(ts)[:m]


# ------------------------------------------------------------ stats helper


def test_latency_stats_empty_and_known():
    empty = latency_stats([])
    assert empty.count == 0 and "--" in empty.brief()
    s = latency_stats([0.001] * 99 + [0.101])
    assert s.count == 100
    assert abs(s.p50_ms - 1.0) < 1e-6
    assert s.p99_ms > s.p95_ms >= s.p50_ms
    assert "p95=" in s.brief()


def test_engine_config_shapes():
    assert CFG.batch_shapes() == (4, 8, 16)
    assert CFG.pad_shape(1) == 4 and CFG.pad_shape(5) == 8
    assert CFG.pad_shape(16) == 16


# -------------------------------------------- micro-batching bit-identity


def test_micro_batched_results_bitwise_vs_solo(state):
    """Property test: random mixed interleavings through the batch former
    produce results bit-identical to padded per-request execution."""
    backend = _local_backend(state)
    cfg = EngineConfig(max_batch=16, min_shape=4, queue_cap=512,
                       slo_ms=250.0, topn=5)
    eng = RequestEngine(backend, cfg)
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(24):
        m = int(rng.integers(1, 9))
        uu = rng.integers(0, U, m)
        if rng.random() < 0.3:
            reqs.append(eng.submit("topn", users=uu))
        else:
            reqs.append(eng.submit("pair", users=uu,
                                   items=rng.integers(0, P, m)))
        if rng.random() < 0.3:  # interleave draining with arrivals
            eng.pump_reads(max_batches=1)
    assert all(r is not None for r in reqs)
    eng.pump_reads()
    pub = backend.snapshot()
    batched = {r.seq for r in reqs}
    assert len(batched) == 24 and all(r.done.is_set() for r in reqs)
    for r in reqs:
        ref = _solo(backend, pub, r, cfg)
        if r.kind == "pair":
            assert np.array_equal(r.result, ref)
        else:
            assert np.array_equal(r.result[0], ref[0])
            assert np.array_equal(r.result[1], ref[1])
    checked, bad = eng.verify_sample(limit=24)
    assert checked > 0 and bad == 0


def test_batch_former_kind_skip_and_per_kind_deadline_order(state):
    """A same-kind batch skips over other-kind entries without reordering
    either kind; the skipped kind forms the next batch."""
    backend = _local_backend(state)
    eng = RequestEngine(backend, CFG)
    p1 = eng.submit("pair", users=[1, 2, 3], items=[0, 1, 2])
    t1 = eng.submit("topn", users=[4, 5])
    p2 = eng.submit("pair", users=[6, 7], items=[3, 4])
    assert eng.pump_reads(max_batches=1) == 1
    assert p1.done.is_set() and p2.done.is_set() and not t1.done.is_set()
    assert eng.pump_reads(max_batches=1) == 1
    assert t1.done.is_set()


def test_deadline_ordering_across_batches(state):
    backend = _local_backend(state)
    eng = RequestEngine(backend, CFG)
    # max_batch rows each: one request per batch, so execution order is
    # exactly deadline order regardless of submission order
    rows = CFG.max_batch
    late = eng.submit("pair", users=np.zeros(rows, int),
                      items=np.zeros(rows, int), deadline_ms=300.0)
    early = eng.submit("pair", users=np.zeros(rows, int),
                       items=np.zeros(rows, int), deadline_ms=50.0)
    mid = eng.submit("pair", users=np.zeros(rows, int),
                     items=np.zeros(rows, int), deadline_ms=150.0)
    assert eng.pump_reads(max_batches=1) == 1
    assert early.done.is_set() and not mid.done.is_set()
    assert eng.pump_reads(max_batches=1) == 1
    assert mid.done.is_set() and not late.done.is_set()
    eng.pump_reads()
    assert late.done.is_set()


# ---------------------------------------------------------------- admission


def test_admission_sheds_on_overflow(state):
    backend = _local_backend(state)
    eng = RequestEngine(backend, CFG)
    admitted = []
    shed = 0
    for _ in range(20):  # 20 x 8 rows > queue_cap=64
        r = eng.submit("pair", users=np.zeros(8, int), items=np.zeros(8, int))
        if r is None:
            shed += 1
        else:
            admitted.append(r)
    assert sum(r.n_rows for r in admitted) <= CFG.queue_cap
    assert shed > 0 and eng.stats()["shed"]["pair"] == shed
    eng.pump_reads()  # every admitted request still completes
    assert all(r.done.is_set() for r in admitted)
    assert eng.stats()["shed_frac"] == pytest.approx(shed / 20)


def test_oversized_request_rejected(state):
    eng = RequestEngine(_local_backend(state), CFG)
    with pytest.raises(ValueError, match="max_batch"):
        eng.submit("pair", users=np.zeros(CFG.max_batch + 1, int),
                   items=np.zeros(CFG.max_batch + 1, int))


@pytest.mark.parametrize("backend_cls", [LocalBackend, MutableLocalBackend])
def test_gathered_stats_counter_follows_shape_rule(state, backend_cls):
    """``exec.engine.<kind>.gathered_stats`` counts the launches whose batch
    shape takes Eq. (1)'s row statistics from the gathered rows, and leaves
    out those that pass over the whole matrix: every top-N launch, the pair
    launches by the shape rule."""
    backend = backend_cls(buckets.from_state(state, min_bucket=U), SPEC,
                          min_bucket=U)
    # 4 * (k + 1) = 24 rows <= 64 < 16 * (k + 1) = 96
    assert backend.gathers_row_stats("pair", 4)
    assert not backend.gathers_row_stats("pair", 16)
    assert backend.gathers_row_stats("topn", 16)
    o = Observability(sample_rate=0.0, seed=0)
    eng = RequestEngine(backend, CFG, obs=o)
    rng = np.random.default_rng(5)
    for kind, m in [("pair", 3), ("pair", 16), ("pair", 2), ("topn", 1),
                    ("topn", 12)]:
        uu = rng.integers(0, U, m)
        items = rng.integers(0, P, m) if kind == "pair" else None
        assert eng.submit(kind, users=uu, items=items) is not None
        assert eng.pump_reads() == 1
    eng.publish_metrics()
    c = o.registry.snapshot()["counters"]
    assert c["exec.engine.pair.b4.launches"] == 2
    assert c["exec.engine.pair.b16.launches"] == 1
    assert c["exec.engine.pair.gathered_stats"] == 2
    assert c["exec.engine.topn.b4.launches"] == 1
    assert c["exec.engine.topn.b16.launches"] == 1
    assert c["exec.engine.topn.gathered_stats"] == 2


@pytest.mark.parametrize("backend_cls", [LocalBackend, MutableLocalBackend])
def test_backends_serve_a_lane_aligned_state(state, backend_cls):
    """A local backend's rating matrix is zero-padded to a multiple of 128
    columns through writes, and it serves the same bits as the read
    programs on the fitted, unpadded state."""
    backend = backend_cls(buckets.from_state(state, min_bucket=U), SPEC,
                          min_bucket=U)
    rng = np.random.default_rng(8)
    users, items = rng.integers(0, U, 8), rng.integers(0, P, 8)
    ju, ji = jnp.asarray(users, jnp.int32), jnp.asarray(items, jnp.int32)
    got = backend.predict_pairs(backend.snapshot(), users, items)
    want = knn.predict_pairs_graph(state.graph, state.ratings, ju, ji)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    gi, gs = backend.recommend_topn(backend.snapshot(), users, 5)
    wi, ws = knn.recommend_topn_graph(state.graph, state.ratings, ju, n=5)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gs), np.asarray(ws))

    rows = _ratings(2, P, seed=12)
    if backend_cls is MutableLocalBackend:
        backend.apply_update(np.array([1, 7]), rows)
        backend.apply_remove(np.array([3]))
        at = [1, 7]
    else:
        backend.fold_in(rows, 8)
        at = [U, U + 1]
    bst = backend.snapshot()[0]
    bst = getattr(bst, "bstate", bst)
    r = np.asarray(bst.state.ratings)
    assert bst.n_items == P and r.shape[1] == 128
    assert not r[:, P:].any()
    assert np.array_equal(r[at, :P], rows)
    if backend_cls is MutableLocalBackend:
        assert not r[3].any()


# ---------------------------------------------------------------- fold lane


def test_fold_swaps_generation_and_new_users_serve(state):
    backend = _local_backend(state)
    eng = RequestEngine(backend, CFG)
    assert backend.generation == 0 and backend.n_users == U
    eng.submit("fold", rows=_ratings(8, P, seed=9))
    assert eng.pump_folds() == 1
    assert backend.generation == 1 and backend.n_users == U + 8
    r = eng.submit("pair", users=np.arange(U, U + 8),
                   items=np.zeros(8, int))
    eng.pump_reads()
    assert r.done.is_set() and np.isfinite(r.result).all()
    assert r.generation == 1


def test_verify_ring_cleared_on_fold(state):
    backend = _local_backend(state)
    eng = RequestEngine(backend, CFG)
    eng.submit("pair", users=[0, 1], items=[0, 1])
    eng.pump_reads()
    eng.submit("fold", rows=_ratings(8, P, seed=10))
    eng.pump_folds()
    checked, bad = eng.verify_sample()  # stale-generation entries retired
    assert checked == 0 and bad == 0
    eng.submit("pair", users=[2, 3], items=[2, 3])
    eng.pump_reads()
    checked, bad = eng.verify_sample()
    assert checked == 1 and bad == 0


def test_fold_lane_never_blocks_reads(state):
    """A slow in-flight fold must not delay read batches (single-device
    backend: true overlap, serialize_folds is False)."""

    class SlowFold(LocalBackend):
        def fold_in(self, rows, bq):
            time.sleep(0.5)
            return super().fold_in(rows, bq)

    backend = SlowFold(buckets.from_state(state, min_bucket=U), SPEC,
                       min_bucket=U)
    assert not backend.serialize_folds
    eng = RequestEngine(backend, CFG)
    # warm the read path so the threaded read is compile-free
    eng.submit("pair", users=[0], items=[0])
    eng.pump_reads()
    eng.start()
    try:
        fold = eng.submit("fold", rows=_ratings(8, P, seed=12))
        time.sleep(0.1)  # let the fold thread enter the slow fold
        r = eng.submit("pair", users=[1, 2], items=[1, 2])
        assert r.done.wait(timeout=0.35), "read stalled behind the fold"
        assert not fold.done.is_set(), "fold finished too fast to prove overlap"
        assert fold.done.wait(timeout=30.0)
    finally:
        eng.stop()
    assert backend.generation == 1


def test_sharded_backend_serializes_fold_launches(state):
    """On a mesh backend the engine must hold exec_lock across folds —
    concurrently-launched collective programs can deadlock the shared
    per-device rendezvous threads on a single-process host mesh."""
    assert ShardedBackend.serialize_folds
    backend = _local_backend(state)
    backend.serialize_folds = True  # exercise the locked path
    eng = RequestEngine(backend, CFG)
    witnessed = []
    orig = backend.fold_in

    def locked_probe(rows, bq):
        witnessed.append(eng.exec_lock.locked())
        return orig(rows, bq)

    backend.fold_in = locked_probe
    eng.submit("fold", rows=_ratings(8, P, seed=13))
    eng.pump_folds()
    assert witnessed == [True]


# ------------------------------------------------- router + sharded engine

needs_mesh = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs 8 host devices")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((2, 4), ("pod", "data"))


@needs_mesh
def test_routed_reads_bitwise_vs_single_device(state, mesh):
    sst = buckets.from_state_sharded(state, mesh, min_bucket=8)
    u_per = -(-U // sst.shard_count)
    id_shard = (np.arange(U) // u_per).astype(np.int32)
    id_slot = (np.arange(U) % u_per).astype(np.int32)
    backend = ShardedBackend(sst, id_shard, id_slot, SPEC, min_bucket=8)
    ref = _local_backend(state)
    rng = np.random.default_rng(4)
    users = rng.integers(0, U, 32)
    items = rng.integers(0, P, 32)
    got = np.asarray(backend.predict_pairs(backend.snapshot(), users, items))
    want = np.asarray(ref.predict_pairs(ref.snapshot(),
                                        users.astype(np.int64),
                                        items.astype(np.int64)))
    assert np.array_equal(got, want)
    gi, gs = backend.recommend_topn(backend.snapshot(), users, 5)
    wi, ws = ref.recommend_topn(ref.snapshot(), users.astype(np.int64), 5)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gs), np.asarray(ws))


@needs_mesh
def test_router_materializes_no_row_space_intermediates(state, mesh):
    sst = buckets.from_state_sharded(state, mesh, min_bucket=8)
    n_avals, bad = materialization_check(sst, b=8, n=5)
    assert n_avals > 0 and bad == []


@needs_mesh
def test_sharded_engine_micro_batching_and_fold(state, mesh):
    sst = buckets.from_state_sharded(state, mesh, min_bucket=8)
    u_per = -(-U // sst.shard_count)
    id_shard = (np.arange(U) // u_per).astype(np.int32)
    id_slot = (np.arange(U) % u_per).astype(np.int32)
    backend = ShardedBackend(sst, id_shard, id_slot, SPEC, min_bucket=8)
    eng = RequestEngine(backend, CFG)
    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(8):
        m = int(rng.integers(1, 9))
        reqs.append(eng.submit("pair", users=rng.integers(0, U, m),
                               items=rng.integers(0, P, m)))
    eng.pump_reads()
    assert all(r.done.is_set() for r in reqs)
    checked, bad = eng.verify_sample()
    assert checked == len(reqs) and bad == 0
    eng.submit("fold", rows=_ratings(8, P, seed=14))
    eng.pump_folds()
    assert backend.generation == 1 and backend.n_users == U + 8
    r = eng.submit("pair", users=np.arange(U, U + 8), items=np.zeros(8, int))
    eng.pump_reads()
    assert np.isfinite(r.result).all()
