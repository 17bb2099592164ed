"""NeighborGraph construction + graph-path prediction parity (the tentpole
refactor: fit's artifact is (U, k), the (U, U) d2 matrix never materializes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LandmarkSpec,
    MEASURES,
    NeighborGraph,
    RatingMatrix,
    build_neighbor_graph,
    build_representation,
    extend_neighbor_graph,
    fit,
    fold_in,
    knn,
    predict,
    predict_dense,
)
from repro.core.landmark_cf import LandmarkState


def _ratings(u, p, density=0.35, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u, p)).astype(np.float32)
    r *= rng.random((u, p)) < density
    return jnp.asarray(r)


@pytest.fixture(scope="module")
def matrix():
    r = _ratings(48, 36, seed=1)
    return RatingMatrix(r, 48, 36)


@pytest.mark.parametrize("measure", MEASURES)
@pytest.mark.parametrize("mode", ["user", "item"])
def test_graph_predictions_match_dense_oracle(matrix, measure, mode):
    """Dense-backend graph path == dense-sims oracle, bit-for-bit: same top-k
    tie-breaking, same Eq. (1) epilogue (self-exclusion, <2-co-rated zeroing
    via 0 weights, mean-centering)."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", d2=measure,
                        mode=mode, k_neighbors=5)
    key = jax.random.PRNGKey(0)
    st_graph = fit(key, matrix, spec, backend="dense")
    st_dense = fit(key, matrix, spec, dense_sims=True)
    assert st_graph.sims is None and st_dense.graph is None

    got = predict_dense(st_graph, spec)
    want = predict_dense(st_dense, spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    rng = np.random.default_rng(3)
    users = jnp.asarray(rng.integers(0, matrix.n_users, 200).astype(np.int32))
    items = jnp.asarray(rng.integers(0, matrix.n_items, 200).astype(np.int32))
    got_p = predict(st_graph, users, items, spec)
    want_p = predict(st_dense, users, items, spec)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


@pytest.mark.parametrize("measure", MEASURES)
def test_streaming_backend_matches_dense_backend(matrix, measure):
    """Streaming chunk-scan graph (with padding: 48 % 16 == 0 but chunk=13
    exercises the ragged tail) predicts within 1e-5 of the dense backend."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", d2=measure,
                        k_neighbors=5)
    key = jax.random.PRNGKey(0)
    st_dense = fit(key, matrix, spec, backend="dense")
    st_stream = fit(key, matrix, spec, backend="streaming")
    # force the ragged-chunk path too (chunk that does not divide U)
    rep = st_dense.representation
    g_ragged = build_neighbor_graph(rep, measure, k=5, backend="streaming",
                                    chunk=13)
    for st in (st_stream,):
        np.testing.assert_allclose(
            np.asarray(predict_dense(st, spec)),
            np.asarray(predict_dense(st_dense, spec)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(knn.predict_all_graph(g_ragged, st_dense.ratings)),
        np.asarray(predict_dense(st_dense, spec)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("measure", MEASURES)
def test_pallas_backend_matches_dense_backend(matrix, measure):
    """Fused Pallas sims+top-k (interpret mode on CPU) serves every d2
    measure — cosine via pre-normalized rows, pearson/euclidean via the
    in-kernel epilogues — with non-multiple-of-block shapes via padding and
    self-exclusion in-kernel."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", d2=measure,
                        k_neighbors=5)
    key = jax.random.PRNGKey(0)
    st_dense = fit(key, matrix, spec, backend="dense")
    st_pallas = fit(key, matrix, spec, backend="pallas")
    assert not (np.asarray(st_pallas.graph.indices)
                == np.arange(matrix.n_users)[:, None]).any()  # no self loops
    np.testing.assert_allclose(
        np.asarray(predict_dense(st_pallas, spec)),
        np.asarray(predict_dense(st_dense, spec)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("measure", ["pearson", "euclidean"])
def test_pallas_fold_in_non_cosine(measure):
    """The fold-in (skinny-query) kernel runs the same in-kernel epilogues,
    so serve-path extends no longer fall back to streaming off-TPU either."""
    u, b, p = 300, 12, 64
    r = _ratings(u + b, p, seed=2)
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", d2=measure,
                        k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r[:u], u, p), spec,
             backend="dense")
    fold_p = fold_in(st, r[u:], spec, backend="pallas")
    fold_d = fold_in(st, r[u:], spec, backend="dense")
    rng = np.random.default_rng(4)
    users = jnp.asarray(rng.integers(0, r.shape[0], 300).astype(np.int32))
    items = jnp.asarray(rng.integers(0, r.shape[1], 300).astype(np.int32))
    np.testing.assert_allclose(
        np.asarray(predict(fold_p, users, items, spec)),
        np.asarray(predict(fold_d, users, items, spec)), rtol=1e-5, atol=1e-5)


def test_graph_k_clamped_to_n_rows():
    g = build_neighbor_graph(jnp.eye(4), "cosine", k=13, backend="dense")
    assert g.k == 3  # k clamps to U-1: a row has at most U-1 neighbors


def _all_avals(jaxpr, out):
    """Recursively collect every intermediate aval in a (closed) jaxpr."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(v.aval)
        for p in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    p, is_leaf=lambda x: hasattr(x, "jaxpr") or hasattr(x, "eqns")):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _all_avals(inner, out)
    return out


def test_default_fit_and_predict_never_allocate_dense_sims():
    """Acceptance: on a 20k-user block, default fit + predict_dense trace to a
    jaxpr with NO (U, U) intermediate anywhere — fit memory is O(U·(n+k))."""
    u, p = 20_000, 64
    spec = LandmarkSpec(n_landmarks=16, selection="popularity", k_neighbors=13)

    def pipeline(key, ratings):
        st = fit(key, RatingMatrix(ratings, u, p), spec)
        return predict_dense(st, spec)

    jaxpr = jax.make_jaxpr(pipeline)(
        jax.random.PRNGKey(0), jnp.zeros((u, p), jnp.float32))
    avals = _all_avals(jaxpr.jaxpr, [])
    offender = [a for a in avals
                if getattr(a, "shape", None) is not None
                and len(getattr(a, "shape", ())) >= 2
                and a.shape.count(u) >= 2]
    assert not offender, f"dense (U, U) intermediates found: {offender[:3]}"
    # sanity: the graph itself IS part of the trace — (U, k) avals exist
    assert any(getattr(a, "shape", None) == (u, spec.k_neighbors) for a in avals)


# --------------------------------------------------------------- serve: fold-in


def _foldin_fixture(u=300, b=12, p=64, k=5, seed=2):
    r = _ratings(u + b, p, seed=seed)
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=k)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r[:u], u, p), spec,
             backend="dense")
    return r, spec, st


def _from_scratch_same_landmarks(r, st, spec):
    """From-scratch fit on the concatenated matrix, landmarks forced to the
    fitted state's (they index rows < U, identical in both matrices)."""
    rep = build_representation(r, st.landmark_idx, spec.d1)
    g = build_neighbor_graph(rep, spec.d2, spec.k_neighbors, backend="dense")
    return LandmarkState(st.landmark_idx, rep, r, graph=g)


@pytest.mark.parametrize("backend", ["dense", "streaming", "pallas"])
def test_fold_in_matches_from_scratch_fit(backend):
    """Acceptance: fold_in of b new users == from-scratch fit on the
    concatenated matrix (same landmarks) within 1e-5, on every extend
    backend (pallas in interpret mode on CPU)."""
    r, spec, st = _foldin_fixture()
    u = st.ratings.shape[0]
    st_fold = fold_in(st, r[u:], spec, backend=backend)
    st_oracle = _from_scratch_same_landmarks(r, st, spec)

    rng = np.random.default_rng(4)
    users = jnp.asarray(rng.integers(0, r.shape[0], 400).astype(np.int32))
    items = jnp.asarray(rng.integers(0, r.shape[1], 400).astype(np.int32))
    np.testing.assert_allclose(
        np.asarray(predict(st_fold, users, items, spec)),
        np.asarray(predict(st_oracle, users, items, spec)),
        rtol=1e-5, atol=1e-5)


def test_fold_in_never_materializes_square_sims():
    """Acceptance: the traced fold_in jaxpr holds no (U, U), (U+b, U+b) or
    (U, U+b) intermediate — the update is O(U·(n+k+b)), not a refit."""
    u, b, p = 300, 12, 64
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    r = _ratings(u + b, p, seed=2)
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r[:u], u, p), spec)

    jaxpr = jax.make_jaxpr(
        lambda s, new: fold_in(s, new, spec, backend="streaming"))(st, r[u:])
    avals = _all_avals(jaxpr.jaxpr, [])
    offender = [a for a in avals
                if getattr(a, "shape", None) is not None
                and len(getattr(a, "shape", ())) >= 2
                and sum(1 for d in a.shape if d in (u, u + b)) >= 2]
    assert not offender, f"square sims intermediates found: {offender[:3]}"
    # sanity: the extended graph IS in the trace
    assert any(getattr(a, "shape", None) == (u + b, spec.k_neighbors)
               for a in avals)


def test_fold_in_back_patches_existing_rows():
    """A new user identical to an existing one (cosine sim 1.0) must enter
    that existing user's neighbor list — the back-patch half of extend."""
    r, spec, st = _foldin_fixture()
    u = st.ratings.shape[0]
    clone_of = 7
    new = jnp.concatenate([r[u:-1], st.ratings[clone_of:clone_of + 1]])
    st_fold = fold_in(st, new, spec)
    clone_id = u + new.shape[0] - 1
    row = np.asarray(st_fold.graph.indices[clone_of])
    assert clone_id in row, (row, clone_id)
    w = np.asarray(st_fold.graph.weights[clone_of])
    np.testing.assert_allclose(w[list(row).index(clone_id)], 1.0, atol=1e-5)


def test_fold_in_composes():
    """Two successive fold-ins == one bigger fold-in (back-patch keeps the
    intermediate graph consistent)."""
    r, spec, st = _foldin_fixture()
    u = st.ratings.shape[0]
    mid = u + 6
    once = fold_in(st, r[u:], spec)
    twice = fold_in(fold_in(st, r[u:mid], spec), r[mid:], spec)
    np.testing.assert_allclose(np.asarray(once.graph.weights),
                               np.asarray(twice.graph.weights),
                               rtol=1e-5, atol=1e-5)
    rng = np.random.default_rng(5)
    users = jnp.asarray(rng.integers(0, r.shape[0], 200).astype(np.int32))
    items = jnp.asarray(rng.integers(0, r.shape[1], 200).astype(np.int32))
    np.testing.assert_allclose(
        np.asarray(predict(once, users, items, spec)),
        np.asarray(predict(twice, users, items, spec)),
        rtol=1e-5, atol=1e-5)


def test_fold_in_rejects_dense_state(matrix):
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), matrix, spec, dense_sims=True)
    with pytest.raises(ValueError, match="graph-backed"):
        fold_in(st, matrix.ratings[:2], spec)


def test_extend_widens_compact_graph():
    r, spec, st = _foldin_fixture()
    u = st.ratings.shape[0]
    g = extend_neighbor_graph(st.graph.to_compact(), st.representation,
                              st.representation[:4] + 0.01, spec.d2)
    assert g.indices.dtype == jnp.int32 and g.weights.dtype == jnp.float32
    assert g.n_nodes == u + 4


# ------------------------------------------------------- serve: compact storage


def test_compact_graph_roundtrip_matches_full(matrix):
    """uint16 ids round-trip exactly; bf16 weights keep predictions within
    bf16 tolerance of the f32/int32 graph."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), matrix, spec)
    g, gc = st.graph, st.graph.to_compact()
    assert gc.indices.dtype == jnp.uint16 and gc.weights.dtype == jnp.bfloat16
    assert gc.is_compact and not g.is_compact
    assert (gc.indices.nbytes + gc.weights.nbytes) * 2 == \
        g.indices.nbytes + g.weights.nbytes

    gf = gc.to_full()
    np.testing.assert_array_equal(np.asarray(gf.indices), np.asarray(g.indices))
    np.testing.assert_allclose(np.asarray(gf.weights), np.asarray(g.weights),
                               rtol=8e-3, atol=8e-3)

    # a compact graph predicts directly (gathers take uint16, bf16 promotes)
    np.testing.assert_allclose(
        np.asarray(knn.predict_all_graph(gc, st.ratings)),
        np.asarray(knn.predict_all_graph(g, st.ratings)),
        rtol=2e-2, atol=2e-2)


def test_compact_rejects_large_u():
    g = NeighborGraph(jnp.zeros((70_000, 2), jnp.int32), jnp.ones((70_000, 2)))
    with pytest.raises(ValueError, match="65535"):
        g.to_compact()


# ------------------------------------------------------------ serve: cold start


def test_cold_start_all_zero_weights_falls_back_to_user_mean(matrix):
    """A user whose graph row is all zero weights (< 2 co-rated everywhere)
    must predict their own mean — never NaN."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), matrix, spec)
    cold = 3
    g = NeighborGraph(st.graph.indices,
                      st.graph.weights.at[cold].set(0.0))
    items = jnp.arange(8, dtype=jnp.int32)
    users = jnp.full((8,), cold, jnp.int32)
    got = np.asarray(knn.predict_pairs_graph(g, st.ratings, users, items))
    mask = np.asarray(matrix.ratings[cold]) != 0
    mean = float(np.asarray(matrix.ratings[cold])[mask].mean())
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, mean, rtol=1e-5)

    # top-N stays finite too (scores are the mean, ranking arbitrary)
    rec_items, scores = knn.recommend_topn_graph(g, st.ratings, users[:1], n=4)
    assert np.isfinite(np.asarray(scores)).all()
    assert not mask[np.asarray(rec_items)[0]].any()  # never re-recommend


def test_recommend_topn_exhausted_slots_are_sentinel(matrix):
    """A user with fewer than n unrated items must get -1/-inf filler slots,
    never a rated item recycled through the -inf tie-break."""
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), matrix, spec)
    u = 5
    ratings = st.ratings.at[u].set(4.0).at[u, :2].set(0.0)  # 2 unrated items
    items, scores = knn.recommend_topn_graph(st.graph, ratings,
                                             jnp.asarray([u]), n=6)
    items, scores = np.asarray(items)[0], np.asarray(scores)[0]
    assert set(items[np.isfinite(scores)]) <= {0, 1}
    assert (items[~np.isfinite(scores)] == -1).all()
    assert (~np.isfinite(scores)).sum() == 4


def test_recommend_topn_excludes_rated_items(matrix):
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), matrix, spec)
    users = jnp.arange(6, dtype=jnp.int32)
    items, scores = knn.recommend_topn_graph(st.graph, st.ratings, users, n=5)
    rated = np.asarray(matrix.ratings) != 0
    for i, u in enumerate(np.asarray(users)):
        assert not rated[u][np.asarray(items)[i]].any()
    assert np.isfinite(np.asarray(scores)).all()


def test_neighbor_graph_pytree_roundtrip():
    g = NeighborGraph(jnp.zeros((4, 2), jnp.int32), jnp.ones((4, 2)))
    leaves, treedef = jax.tree_util.tree_flatten(g)
    g2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(g2, NeighborGraph) and g2.n_nodes == 4 and g2.k == 2


# ------------------------------------------- serve: gathered row statistics

ROWS_K, ROWS_P, ROWS_VALID, ROWS_CAP = 3, 10, 10, 12
# user 0 cites a cold, a padded and a tombstoned row; 2 rated every item; 4 is cold
ROWS_USERS = [0, 5, 2, 4]


def _row_stats_state(cap):
    """A ``cap``-row bucket: rows ``>= ROWS_VALID`` are padding holding stale
    ratings; user 0's neighbours are a cold (all-zero) row, a padded row and
    a tombstoned row; user 2 has rated every item. Rows past ``ROWS_CAP``
    are empty bucket slots."""
    rng = np.random.default_rng(7)
    r = rng.integers(1, 6, (ROWS_CAP, ROWS_P)).astype(np.float32)
    r *= rng.random((ROWS_CAP, ROWS_P)) < 0.5
    r[4] = 0.0
    r[2] = rng.integers(1, 6, ROWS_P)
    idx = rng.integers(0, ROWS_CAP, (ROWS_CAP, ROWS_K)).astype(np.int32)
    idx[0] = [4, 11, 7]
    w = rng.standard_normal((ROWS_CAP, ROWS_K)).astype(np.float32)
    tomb = np.zeros(ROWS_CAP, bool)
    tomb[7] = True
    pad = cap - ROWS_CAP
    graph = NeighborGraph(jnp.asarray(np.pad(idx, ((0, pad), (0, 0)))),
                          jnp.asarray(np.pad(w, ((0, pad), (0, 0)))))
    return (graph, jnp.asarray(np.pad(r, ((0, pad), (0, 0)))),
            jnp.asarray(np.pad(tomb, (0, pad))))


def _read(kind, cap, users):
    graph, ratings, tomb = _row_stats_state(cap)
    users = jnp.asarray(users, jnp.int32)
    kw = dict(n_valid=jnp.int32(ROWS_VALID), tomb=tomb)
    if kind == "pair":
        items = (users * 3 + 1) % ROWS_P
        return (np.asarray(knn.predict_pairs_graph(graph, ratings, users,
                                                   items, **kw)),)
    it, sc = knn.recommend_topn_graph(graph, ratings, users, n=4, **kw)
    return np.asarray(it), np.asarray(sc)


def _topn_whole_matrix(users, n=4):
    """Top-N with the row statistics of one pass over the whole matrix:
    the oracle of the gathered-row top-N program."""
    graph, ratings, tomb = _row_stats_state(64)
    users = jnp.asarray(users, jnp.int32)
    mask, means, centered = knn._center(ratings)
    idx = graph.indices[users]
    w = knn._mask_padded_rows(idx, graph.weights[users],
                              jnp.int32(ROWS_VALID), tomb=tomb)
    preds = knn._block_predict(w, centered[idx], mask[idx], means[users])
    preds = jnp.where(mask[users] > 0, -jnp.inf, preds)
    scores, items = jax.lax.top_k(preds, n)
    return (np.asarray(jnp.where(jnp.isfinite(scores), items, -1)),
            np.asarray(scores))


@pytest.mark.parametrize("b, cap, gathered", [(2, ROWS_CAP, True),
                                              (4, ROWS_CAP, False),
                                              (4, 64, True)])
@pytest.mark.parametrize("kind", ["pair", "topn"])
def test_gathered_row_stats_bitwise_vs_whole_matrix(kind, b, cap, gathered):
    """Eq. (1)'s row statistics taken from the gathered rows give the same
    bits as the pass over the whole matrix, through padding, tombstones, a
    cold neighbour and an exhausted user. Pair batches of ``b`` rows fall
    on either side of the shape rule and are held to a whole-matrix pair
    batch; the top-N program always gathers and is held to its oracle."""
    assert knn.gathers_row_stats(b, ROWS_K, cap) is gathered
    users = (ROWS_USERS * 8)[:b]
    ref_b = 20  # 20 * (k + 1) rows > 64: the whole-matrix path
    assert not knn.gathers_row_stats(ref_b, ROWS_K, 64)
    want = (_read(kind, 64, (ROWS_USERS * 8)[:ref_b]) if kind == "pair"
            else _topn_whole_matrix((ROWS_USERS * 8)[:ref_b]))
    got = _read(kind, cap, users)
    for g, w_ in zip(got, want):
        assert np.array_equal(g, w_[:b])
    assert np.isfinite(got[-1][:2]).all()
    if kind == "topn" and b > 2:  # user 2 rated every item: all exhausted
        assert (got[0][2] == -1).all() and np.isneginf(got[1][2]).all()


def test_topn_on_widened_rows_never_lists_padding_columns():
    """Zero columns of a lane-aligned rating matrix (``buckets``) are no
    item: with ``n_items`` the lists equal those of the matrix as it is,
    and an exhausted user gets -1 / -inf slots, never a padding column."""
    graph, ratings, tomb = _row_stats_state(ROWS_CAP)
    wide = jnp.pad(ratings, ((0, 0), (0, 128 - ROWS_P)))
    users = jnp.asarray(ROWS_USERS, jnp.int32)
    kw = dict(n=4, n_valid=jnp.int32(ROWS_VALID), tomb=tomb)
    wi, ws = knn.recommend_topn_graph(graph, ratings, users, **kw)
    gi, gs = knn.recommend_topn_graph(graph, wide, users, n_items=ROWS_P,
                                      **kw)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gs), np.asarray(ws))
    assert (np.asarray(gi)[2] == -1).all()


@pytest.mark.parametrize("b", [8, 128])
@pytest.mark.parametrize("kind", ["pair", "topn"])
def test_read_programs_hold_no_row_space_intermediate(kind, b):
    """At serving shapes the traced read programs hold nothing with the
    matrix's full row dimension apart from their inputs: the mask, means
    and centred values come from the (b, k, P) gathered rows."""
    k, p = 13, 64
    cap = 16 * b * (k + 1)
    sds = jax.ShapeDtypeStruct
    graph = NeighborGraph(sds((cap, k), jnp.int32), sds((cap, k), jnp.float32))
    args = (graph, sds((cap, p), jnp.float32), sds((b,), jnp.int32))
    kw = dict(n_valid=sds((), jnp.int32), tomb=sds((cap,), jnp.bool_))
    if kind == "pair":
        def read(g, r, u, nv, t):
            return knn.predict_pairs_graph(g, r, u, u, n_valid=nv, tomb=t)
    else:
        def read(g, r, u, nv, t):
            return knn.recommend_topn_graph(g, r, u, n=10, n_valid=nv,
                                            tomb=t)
    jaxpr = jax.make_jaxpr(read)(*args, *kw.values())
    avals = _all_avals(jaxpr.jaxpr, [])
    offender = [a for a in avals if cap in getattr(a, "shape", ())]
    assert not offender, f"row-space intermediates: {offender[:3]}"
    assert any(getattr(a, "shape", None) == (b, k, p) for a in avals)
