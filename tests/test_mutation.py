"""Write-path mutation subsystem (docs/mutation.md): in-place updates, GDPR
deletion, decremental repair, compaction — bitwise oracle-exact against a
from-scratch fit on the mutated matrix with the same frozen landmark basis.

Sizes are 8-aligned on purpose (U=96, batches of 8, 88 survivors after an
8-row removal): per-element GEMM bitwise stability across different batch
shapes holds when the candidate (column) dimension is 8-aligned, and the
engine write lane pads mutation batches to 8 for exactly this reason.
"""
import os

if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import mutation
from repro.core.graph import build_neighbor_graph, canonical_topk, merge_canonical_topk
from repro.core.landmark_cf import fit
from repro.core.similarity import masked_similarity
from repro.core.types import LandmarkSpec, RatingMatrix
from repro.launch.mesh import make_mesh
from repro.lifecycle import buckets

U, P = 96, 40
MEASURES = ("cosine", "pearson", "euclidean")

needs_mesh = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs 8 host devices")


def _ratings(u, p, seed=0, density=0.35):
    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u, p)).astype(np.float32)
    return jnp.asarray(r * (rng.random((u, p)) < density))


def _spec(d2="cosine", k=7, n=12):
    return LandmarkSpec(n_landmarks=n, selection="popularity",
                        k_neighbors=k, d2=d2)


def _oracle(matrix, landmarks, spec):
    """From-scratch rep + graph on ``matrix`` with the frozen basis."""
    rep = masked_similarity(matrix, landmarks, spec.d1)
    graph = build_neighbor_graph(rep, spec.d2, spec.k_neighbors)
    return rep, graph


def _pad_update(ids, rows, b=8):
    """Pad an update batch to the engine lane's minimum shape."""
    m = len(ids)
    pids = jnp.full((b,), -1, jnp.int32).at[:m].set(jnp.asarray(ids, jnp.int32))
    prows = jnp.zeros((b, rows.shape[1]), jnp.float32).at[:m].set(
        jnp.asarray(rows, jnp.float32))
    return pids, prows, jnp.int32(m)


def _assert_no_tomb_citations(mst, dead):
    """No live row's list may cite a tombstoned id (inert slots excepted)."""
    g = mst.bstate.state.graph
    gi, gw = np.asarray(g.indices), np.asarray(g.weights)
    n_valid = int(mst.bstate.n_valid)
    tomb = np.asarray(mst.tomb)
    live = np.nonzero(~tomb[:n_valid])[0]
    cit = np.isin(gi[live], np.asarray(dead)) & ~((gi[live] == 0) & (gw[live] == 0.0))
    assert not cit.any(), "tombstoned id cited by a live neighbor list"


# ----------------------------------------------------------------- update
@pytest.mark.parametrize("d2", MEASURES)
def test_update_ratings_bitwise_oracle(d2):
    """update + drained repairs == from-scratch fit on the mutated matrix
    with the frozen landmarks — ratings, representation, and graph bitwise."""
    spec = _spec(d2)
    r = _ratings(U, P, seed=1)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)

    rng = np.random.default_rng(2)
    ids = [0, 3, 50, 95]  # id 0 may be a landmark — the basis must not move
    rows = (rng.integers(0, 6, (4, P)).astype(np.float32)
            * (rng.random((4, P)) < 0.4))
    pids, prows, bv = _pad_update(ids, rows)
    mst = mutation.update_ratings(mst, pids, prows, bv, spec)
    mst = mutation.drain_repairs(mst, spec, bq=32)
    assert mst.dirty_count() == 0

    rm = np.asarray(r).copy()
    rm[ids] = rows
    rep_o, graph_o = _oracle(jnp.asarray(rm), mst.landmarks, spec)
    got = mst.bstate.state
    np.testing.assert_array_equal(np.asarray(got.ratings[:U]), rm)
    np.testing.assert_array_equal(np.asarray(got.representation[:U]),
                                  np.asarray(rep_o))
    np.testing.assert_array_equal(np.asarray(got.graph.indices[:U]),
                                  np.asarray(graph_o.indices))
    np.testing.assert_array_equal(np.asarray(got.graph.weights[:U]),
                                  np.asarray(graph_o.weights))


def test_update_ignores_invalid_and_tombstoned_ids():
    """Out-of-range, negative, and tombstoned targets are dropped — the
    batch behaves exactly like one containing only its valid entries."""
    spec = _spec()
    r = _ratings(U, P, seed=4)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    rng = np.random.default_rng(5)
    row = (rng.integers(1, 6, (1, P)).astype(np.float32)
           * (rng.random((1, P)) < 0.4))

    dead = list(range(5, 13))  # 8-aligned removal
    base = mutation.remove_users(mutation.from_fitted(st),
                                 jnp.asarray(dead, jnp.int32), jnp.int32(8))

    noisy_ids, noisy_rows, _ = _pad_update([5, 10_000, -3, 7],
                                           np.repeat(row, 4, axis=0))
    a = mutation.update_ratings(base, noisy_ids, noisy_rows, jnp.int32(4), spec)
    clean_ids, clean_rows, bv = _pad_update([7], row)
    b = mutation.update_ratings(base, clean_ids, clean_rows, bv, spec)

    for xa, xb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    assert not np.asarray(a.bstate.state.ratings[5]).any(), \
        "update resurrected a tombstoned row"


def test_update_ratings_never_materializes_row_space():
    """The traced update jaxpr holds no (capacity, capacity) intermediate —
    graph maintenance is the skinny (capacity, b) back-patch block."""
    spec = _spec()
    r = _ratings(U, P, seed=6)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)
    cap = mst.capacity
    ids = jnp.zeros((8,), jnp.int32)
    rows = jnp.zeros((8, P), jnp.float32)

    jaxpr = jax.make_jaxpr(
        lambda m, i, ro: mutation.update_ratings(m, i, ro, jnp.int32(8), spec)
    )(mst, ids, rows)

    def collect(jx, out):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                out.append(v.aval)
            for p_ in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        p_, is_leaf=lambda x: hasattr(x, "jaxpr")
                        or hasattr(x, "eqns")):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        collect(inner, out)
        return out

    avals = collect(jaxpr.jaxpr, [])
    offender = [a for a in avals
                if getattr(a, "shape", None) is not None
                and sum(1 for d in getattr(a, "shape", ()) if d == cap) >= 2]
    assert not offender, f"row-space intermediates found: {offender[:3]}"
    assert any(getattr(a, "shape", None) == (cap, 8) for a in avals), \
        "expected the (capacity, b) back-patch block in the trace"


# ----------------------------------------------------------------- remove
@pytest.mark.parametrize("d2", MEASURES)
def test_remove_compact_bitwise_oracle(d2):
    """remove → (absence holds immediately) → drain → compact == fit on the
    surviving 88-row matrix with the frozen landmarks, bitwise."""
    spec = _spec(d2)
    r = _ratings(U, P, seed=3)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)

    dead = np.array([3, 8, 17, 20, 40, 41, 77, 95], np.int32)
    mst = mutation.remove_users(mst, jnp.asarray(dead), jnp.int32(8))
    # erasure + absence BEFORE any repair ran
    assert not np.asarray(mst.bstate.state.ratings)[dead].any()
    assert not np.asarray(mst.bstate.state.representation)[dead].any()
    _assert_no_tomb_citations(mst, dead)
    assert mst.tombstone_frac() == pytest.approx(8 / 96)
    assert mst.n_live() == 88

    mst = mutation.drain_repairs(mst, spec, bq=32)
    mstc = mutation.compact_tombstones(mst)
    assert mstc.tombstone_frac() == 0.0

    live = np.setdiff1d(np.arange(U), dead)
    rep_o, graph_o = _oracle(r[live], mst.landmarks, spec)
    got = mstc.bstate.state
    n = len(live)
    np.testing.assert_array_equal(np.asarray(got.ratings[:n]),
                                  np.asarray(r)[live])
    np.testing.assert_array_equal(np.asarray(got.representation[:n]),
                                  np.asarray(rep_o))
    np.testing.assert_array_equal(np.asarray(got.graph.indices[:n]),
                                  np.asarray(graph_o.indices))
    np.testing.assert_array_equal(np.asarray(got.graph.weights[:n]),
                                  np.asarray(graph_o.weights))


def test_fold_in_mutable_excludes_tombstoned_candidates():
    """Fold-in after removals (pre-compaction) must not cite tombstones —
    euclidean is the trap: a zeroed representation still scores positive."""
    spec = _spec("euclidean")
    r = _ratings(U, P, seed=7)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)
    dead = np.array([0, 1, 2, 3, 4, 5, 6, 7], np.int32)
    mst = mutation.remove_users(mst, jnp.asarray(dead), jnp.int32(8))

    new_rows = np.asarray(_ratings(8, P, seed=8))
    mst = mutation.fold_in_rows(mst, new_rows, bq=8, spec=spec)
    _assert_no_tomb_citations(mst, dead)
    mst = mutation.drain_repairs(mst, spec, bq=32)
    _assert_no_tomb_citations(mst, dead)

    # the folded rows serve
    new_ids = jnp.arange(U, U + 8, dtype=jnp.int32)
    preds = mutation.predict_pairs(mst, new_ids,
                                   jnp.arange(8, dtype=jnp.int32))
    assert np.isfinite(np.asarray(preds)).all()


# ----------------------------------------------------------------- repair
def test_repair_ivf_full_probe_matches_rescan():
    """IVF-backed repair at full probe is bitwise the full-rescan repair."""
    from repro.retrieval import IVFSpec, build_index, resolve_ivf

    spec = _spec(d2="cosine", k=5, n=8)
    r = _ratings(U, P, seed=9)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)
    ids, rows, bv = _pad_update([5, 30, 60],
                                np.asarray(_ratings(3, P, seed=10)))
    mst = mutation.update_ratings(mst, ids, rows, bv, spec)

    C = 8
    cap = mst.capacity
    ivf = build_index(mst.bstate.state.representation,
                      resolve_ivf(IVFSpec(n_clusters=C, nprobe=C), cap),
                      spec.d2, n_valid=mst.bstate.n_valid)
    a = mutation.drain_repairs(mst, spec, bq=16)
    b = mutation.drain_repairs(mst, spec, bq=16, ivf_index=ivf)
    ga, gb = a.bstate.state.graph, b.bstate.state.graph
    np.testing.assert_array_equal(np.asarray(ga.indices), np.asarray(gb.indices))
    np.testing.assert_array_equal(np.asarray(ga.weights), np.asarray(gb.weights))


# ------------------------------------------------------------------ merge
def test_merge_canonical_topk_matches_full_sort():
    """The rank-count merge of two canonical lists == canonical_topk over
    their concatenation — with id tie-breaks and with explicit ranks."""
    rng = np.random.default_rng(11)
    rows, ka, kb, k = 64, 7, 5, 7
    # heavy value ties (small value alphabet) but ids disjoint across lists
    ids = np.stack([rng.choice(200, ka + kb, replace=False)
                    for _ in range(rows)]).astype(np.int32)
    vals = rng.integers(0, 4, (rows, ka + kb)).astype(np.float32) / 2.0

    def canon(v, i, r):
        o = np.lexsort((r, -v), axis=-1)
        take = lambda x: np.take_along_axis(x, o, axis=-1)
        return take(v), take(i), take(r)

    av, ai, ar = canon(vals[:, :ka], ids[:, :ka], ids[:, :ka])
    bv, bi, br = canon(vals[:, ka:], ids[:, ka:], ids[:, ka:])

    mv, mi = merge_canonical_topk(jnp.asarray(av), jnp.asarray(ai),
                                  jnp.asarray(bv), jnp.asarray(bi), k)
    rv, ri = canonical_topk(jnp.asarray(vals), jnp.asarray(ids), k)
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(ri))

    # explicit ranks decoupled from ids (the sharded path's tie order)
    ranks = np.stack([rng.permutation(ka + kb) for _ in range(rows)]
                     ).astype(np.int32)
    av, ai, ar = canon(vals[:, :ka], ids[:, :ka], ranks[:, :ka])
    bv, bi, br = canon(vals[:, ka:], ids[:, ka:], ranks[:, ka:])
    mv, mi = merge_canonical_topk(jnp.asarray(av), jnp.asarray(ai),
                                  jnp.asarray(bv), jnp.asarray(bi), k,
                                  a_rank=jnp.asarray(ar),
                                  b_rank=jnp.asarray(br))
    rv, ri = canonical_topk(jnp.asarray(vals), jnp.asarray(ids), k,
                            rank=jnp.asarray(ranks))
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(ri))


# ----------------------------------------------------------------- sharded
@needs_mesh
def test_sharded_mutation_parity():
    """update / remove / compact / fold-in on the mesh predict bit-identically
    to the single-device mutable path (modulo the sharded-id bijection)."""
    from repro.mutation import sharded as muts

    mesh = make_mesh((4,), ("pod",))
    spec = _spec("pearson")
    r = _ratings(U, P, seed=12)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    mst = mutation.from_fitted(st)
    sst = buckets.from_state_sharded(st, mesh, row_axes=("pod",), min_bucket=8)
    msst = muts.from_sharded(sst)
    C = msst.capacity
    u_per = U // 4
    smap = lambda logical: (np.asarray(logical) // u_per) * C \
        + np.asarray(logical) % u_per

    rng = np.random.default_rng(13)
    up = np.array([3, 50, 95, 0], np.int32)
    rows = np.asarray(_ratings(4, P, seed=14))
    ids, prows, bv = _pad_update(up, rows)
    sids, _, _ = _pad_update(smap(up), rows)
    mst = mutation.drain_repairs(
        mutation.update_ratings(mst, ids, prows, bv, spec), spec, bq=16)
    msst = muts.drain_repairs_sharded(
        muts.update_ratings_sharded(msst, sids, prows, bv, spec), spec, bq=16)

    users = rng.integers(0, U, 200).astype(np.int32)
    items = jnp.asarray(rng.integers(0, P, 200).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(mutation.predict_pairs(mst, jnp.asarray(users), items)),
        np.asarray(muts.predict_pairs(
            msst, jnp.asarray(smap(users).astype(np.int32)), items)))

    dead = np.array([10, 11, 95, 20, 33, 40, 41, 77], np.int32)
    mst = mutation.remove_users(mst, jnp.asarray(dead), jnp.int32(8))
    msst = muts.remove_users_sharded(
        msst, jnp.asarray(smap(dead).astype(np.int32)), jnp.int32(8))
    live = np.setdiff1d(np.arange(U), dead)
    mst = mutation.drain_repairs(mst, spec, bq=16)
    msst = muts.drain_repairs_sharded(msst, spec, bq=16)
    lu = live[rng.integers(0, len(live), 200)]
    np.testing.assert_array_equal(
        np.asarray(mutation.predict_pairs(
            mst, jnp.asarray(lu.astype(np.int32)), items)),
        np.asarray(muts.predict_pairs(
            msst, jnp.asarray(smap(lu).astype(np.int32)), items)))

    # compaction: renumbered ids still agree
    tomb = np.asarray(msst.tomb)
    nv = np.asarray(msst.sstate.n_valid)
    new_slot = {}
    for s in range(4):
        cnt = 0
        for slot in range(int(nv[s])):
            if not tomb[s * C + slot]:
                new_slot[s * C + slot] = s * C + cnt
                cnt += 1
    mstc = mutation.compact_tombstones(mst)
    msstc = muts.compact_tombstones_sharded(msst)
    dense_map = {old: new for new, old in enumerate(live)}
    lu2 = live[rng.integers(0, len(live), 200)]
    np.testing.assert_array_equal(
        np.asarray(mutation.predict_pairs(
            mstc, jnp.asarray([dense_map[x] for x in lu2], dtype=jnp.int32),
            items)),
        np.asarray(muts.predict_pairs(
            msstc,
            jnp.asarray([new_slot[smap([x])[0]] for x in lu2],
                        dtype=jnp.int32), items)))

    # fold-in on the compacted states
    new_rows = np.asarray(_ratings(8, P, seed=15))
    mst2 = mutation.drain_repairs(
        mutation.fold_in_rows(mstc, new_rows, bq=8, spec=spec), spec, bq=16)
    msst2, shards, slots = muts.fold_in_rows_sharded(
        msstc, new_rows, bq=8, spec=spec, min_bucket=8)
    msst2 = muts.drain_repairs_sharded(msst2, spec, bq=16)
    C2 = msst2.capacity
    np.testing.assert_array_equal(
        np.asarray(mutation.predict_pairs(
            mst2, jnp.arange(len(live), len(live) + 8, dtype=jnp.int32),
            items[:8])),
        np.asarray(muts.predict_pairs(
            msst2, jnp.asarray((shards * C2 + slots).astype(np.int32)),
            items[:8])))


# ------------------------------------------------------------------ engine
def test_engine_mutation_kinds_local():
    """update/remove ride the engine's write lane: atomic generation swaps,
    drained repairs, live stats, bitwise verify, compacting refresh."""
    from repro.serving import EngineConfig, MutableLocalBackend, RequestEngine

    spec = _spec(d2="cosine", k=5, n=8)
    r = _ratings(U, P, seed=16)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    be = MutableLocalBackend(buckets.from_state(st, min_bucket=32), spec,
                             min_bucket=32)
    eng = RequestEngine(be, EngineConfig(max_batch=32, min_shape=8, fold_bq=8))

    rng = np.random.default_rng(17)
    users = rng.integers(0, U, 16)
    items = rng.integers(0, P, 16)
    r0 = eng.submit("pair", users=users, items=items)
    eng.pump_reads()
    assert r0.done.is_set()

    up_ids = np.array([5, 30, 60])
    up_rows = np.asarray(_ratings(3, P, seed=18))
    rm_ids = np.array([3, 17, 40, 41, 77, 90, 8, 20])
    ru = eng.submit("update", users=up_ids, rows=up_rows)
    rr = eng.submit("remove", users=rm_ids)
    eng.pump_folds()
    assert ru.done.is_set() and rr.done.is_set()
    assert be.generation == 2
    assert be._pub[0].dirty_count() == 0

    r1 = eng.submit("topn", users=users)
    eng.pump_reads()
    assert r1.done.is_set()
    stats = eng.stats()
    assert stats["mutated_rows"] == 11
    assert 0 < stats["tombstone_frac"] < 1
    checked, bad = eng.verify_sample()
    assert bad == 0 and checked > 0

    # post-mutation reads equal the published state's own predictions
    mst_live = be._pub[0]
    _assert_no_tomb_citations(mst_live, rm_ids)
    r2 = eng.submit("pair", users=users, items=items)
    eng.pump_reads()
    np.testing.assert_array_equal(
        np.asarray(r2.result),
        np.asarray(mutation.predict_pairs(
            mst_live, jnp.asarray(users, jnp.int32),
            jnp.asarray(items, jnp.int32))))

    gen, table = be.refresh()
    assert (table[rm_ids] == -1).all()
    assert be._pub[0].tombstone_frac() == 0.0
    live = np.setdiff1d(np.arange(U), rm_ids)
    preds = mutation.predict_pairs(
        be._pub[0], jnp.asarray(table[live[:8]], jnp.int32),
        jnp.asarray(items[:8], jnp.int32))
    assert np.isfinite(np.asarray(preds)).all()


@needs_mesh
def test_engine_mutation_kinds_sharded_parity():
    """The sharded engine's routed reads match the single-device mutable
    backend after the same update/remove traffic, and across the compacting
    refresh."""
    from repro.serving import (EngineConfig, MutableLocalBackend,
                               MutableShardedBackend, RequestEngine)

    spec = _spec(d2="cosine", k=5, n=8)
    r = _ratings(U, P, seed=16)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, U, P), spec)
    rng = np.random.default_rng(17)
    users = rng.integers(0, U, 16)
    items = rng.integers(0, P, 16)
    up_ids = np.array([5, 30, 60])
    up_rows = np.asarray(_ratings(3, P, seed=18))
    rm_ids = np.array([3, 17, 40, 41, 77, 90, 8, 20])

    be = MutableLocalBackend(buckets.from_state(st, min_bucket=32), spec,
                             min_bucket=32)
    eng = RequestEngine(be, EngineConfig(max_batch=32, min_shape=8, fold_bq=8))
    eng.submit("update", users=up_ids, rows=up_rows)
    eng.submit("remove", users=rm_ids)
    eng.pump_folds()
    gen, table = None, None

    mesh = make_mesh((4,), ("pod",))
    sstate = buckets.from_state_sharded(st, mesh, row_axes=("pod",),
                                        min_bucket=8)
    u_per = U // 4
    sbe = MutableShardedBackend(sstate, np.arange(U) // u_per,
                                np.arange(U) % u_per, spec, min_bucket=8)
    seng = RequestEngine(sbe, EngineConfig(max_batch=32, min_shape=8,
                                           fold_bq=8))
    seng.submit("update", users=up_ids, rows=up_rows)
    seng.submit("remove", users=rm_ids)
    seng.pump_folds()
    assert sbe._pub[0].dirty_count() == 0

    r3 = seng.submit("pair", users=users, items=items)
    seng.pump_reads()
    want = np.asarray(mutation.predict_pairs(
        be._pub[0], jnp.asarray(users, jnp.int32),
        jnp.asarray(items, jnp.int32)))
    np.testing.assert_array_equal(np.asarray(r3.result), want)

    gen, table = be.refresh()
    gen2, table2 = sbe.refresh()
    assert (table2[rm_ids] == -1).all()
    assert sbe._pub[0].tombstone_frac() == 0.0
    live = np.setdiff1d(np.arange(U), rm_ids)
    r4 = seng.submit("pair", users=live[:8], items=items[:8])
    seng.pump_reads()
    np.testing.assert_array_equal(
        np.asarray(r4.result),
        np.asarray(mutation.predict_pairs(
            be._pub[0], jnp.asarray(table[live[:8]], jnp.int32),
            jnp.asarray(items[:8], jnp.int32))))
