"""Distribution-layer tests on a forced 8-device host platform."""
import os
import sys

import pytest

# These tests need >1 device; spawn-style env var must be set before jax init.
if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import LandmarkSpec  # noqa: E402
from repro.core.landmark_cf import fit, fit_distributed  # noqa: E402
from repro.core.similarity import streaming_knn_graph_sharded, dense_similarity  # noqa: E402
from repro.core.types import RatingMatrix  # noqa: E402
from repro.distributed.embedding import embedding_bag, embedding_lookup  # noqa: E402
from repro.distributed.compression import psum_compressed  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402

pytestmark = pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh()  # (data=2, model=4)


def test_sharded_embedding_lookup_matches_take(mesh):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 64, size=(16, 3)).astype(np.int32))
    want = embedding_lookup(table, ids, mesh=None)
    got = embedding_lookup(table, ids, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # bag reduction parity (torch EmbeddingBag semantics)
    got_bag = embedding_bag(table, ids, "mean", mesh=mesh)
    want_bag = embedding_bag(table, ids, "mean", mesh=None)
    np.testing.assert_allclose(np.asarray(got_bag), np.asarray(want_bag), rtol=1e-6)


def test_fit_distributed_matches_local(mesh):
    rng = np.random.default_rng(1)
    r = rng.integers(1, 6, (64, 40)).astype(np.float32)
    r *= rng.random((64, 40)) < 0.5
    m = RatingMatrix(jnp.asarray(r), 64, 40)
    spec = LandmarkSpec(n_landmarks=8, selection="popularity")
    # dense_sims escape hatch: exact (U, U) parity with the local dense fit
    local = fit(jax.random.PRNGKey(0), m, spec, dense_sims=True)
    dist = fit_distributed(jax.random.PRNGKey(0), m.ratings, spec, mesh,
                           user_axes=("data",), dense_sims=True)
    np.testing.assert_allclose(np.asarray(dist.representation),
                               np.asarray(local.representation), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dist.sims), np.asarray(local.sims),
                               rtol=1e-4, atol=1e-4)


def test_fit_distributed_graph_matches_local_graph(mesh):
    """Default fit_distributed emits the sharded NeighborGraph; its neighbor
    weights must match the single-host dense-backend graph row-for-row."""
    rng = np.random.default_rng(5)
    r = rng.integers(1, 6, (64, 40)).astype(np.float32)
    r *= rng.random((64, 40)) < 0.5
    m = RatingMatrix(jnp.asarray(r), 64, 40)
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    local = fit(jax.random.PRNGKey(0), m, spec, backend="dense")
    dist = fit_distributed(jax.random.PRNGKey(0), m.ratings, spec, mesh,
                           user_axes=("data",))
    assert dist.sims is None
    assert dist.graph.indices.shape == (64, 5)
    np.testing.assert_allclose(np.sort(np.asarray(dist.graph.weights), 1),
                               np.sort(np.asarray(local.graph.weights), 1),
                               rtol=1e-4, atol=1e-4)
    # prediction-level parity (robust to index tie-breaks at equal weight)
    from repro.core import predict

    users = jnp.asarray(rng.integers(0, 64, 128).astype(np.int32))
    items = jnp.asarray(rng.integers(0, 40, 128).astype(np.int32))
    np.testing.assert_allclose(np.asarray(predict(dist, users, items, spec)),
                               np.asarray(predict(local, users, items, spec)),
                               rtol=1e-4, atol=1e-4)


def test_streaming_knn_sharded_matches_dense_topk(mesh):
    rng = np.random.default_rng(2)
    u, n, k = 64, 16, 4
    rep = jnp.asarray(rng.normal(size=(u, n)).astype(np.float32))
    rep_sharded = jax.device_put(rep, NamedSharding(mesh, P(("data",), None)))
    with jax.set_mesh(mesh):
        vals, idx = jax.jit(
            lambda r: streaming_knn_graph_sharded(r, mesh, "cosine", k=k,
                                                  chunk_local=8, row_axes=("data",))
        )(rep_sharded)
    dense = dense_similarity(rep, rep, "cosine")
    want_vals, want_idx = jax.lax.top_k(dense, k)
    np.testing.assert_allclose(np.sort(np.asarray(vals), 1),
                               np.sort(np.asarray(want_vals), 1), rtol=1e-4, atol=1e-4)
    # neighbor sets match row-by-row
    for i in range(u):
        assert set(np.asarray(idx)[i].tolist()) == set(np.asarray(want_idx)[i].tolist())


def test_streaming_knn_sharded_ragged_chunks(mesh):
    """u_local NOT a multiple of chunk_local (20 % 8): the padded candidate
    path must neither crash nor double-count rows, and k > chunk_local must
    still work (one gathered step holds chunk×S candidates)."""
    rng = np.random.default_rng(11)
    u, n, k = 40, 12, 13
    rep = jnp.asarray(rng.normal(size=(u, n)).astype(np.float32))
    rep_sharded = jax.device_put(rep, NamedSharding(mesh, P(("data",), None)))
    with jax.set_mesh(mesh):
        vals, idx = jax.jit(
            lambda r: streaming_knn_graph_sharded(
                r, mesh, "cosine", k=k, chunk_local=8, row_axes=("data",),
                exclude_self=True)
        )(rep_sharded)
    dense = jnp.where(jnp.eye(u, dtype=bool), -jnp.inf,
                      dense_similarity(rep, rep, "cosine"))
    want_vals, want_idx = jax.lax.top_k(dense, k)
    np.testing.assert_allclose(np.sort(np.asarray(vals), 1),
                               np.sort(np.asarray(want_vals), 1),
                               rtol=1e-4, atol=1e-4)
    for i in range(u):
        assert set(np.asarray(idx)[i].tolist()) == set(np.asarray(want_idx)[i].tolist())


@pytest.mark.parametrize("exclude_self", [False, True])
def test_streaming_knn_sharded_multi_axis_global_ids(mesh, exclude_self):
    """8-way sharding over BOTH mesh axes: the gathered-chunk → global-row-id
    mapping must agree with the unsharded oracle (this is the satellite fix
    for the old dead-code id arithmetic in streaming_knn_graph_sharded)."""
    rng = np.random.default_rng(7)
    u, n, k = 64, 12, 4
    rep = jnp.asarray(rng.normal(size=(u, n)).astype(np.float32))
    rep_sharded = jax.device_put(
        rep, NamedSharding(mesh, P(("data", "model"), None)))
    with jax.set_mesh(mesh):
        vals, idx = jax.jit(
            lambda r: streaming_knn_graph_sharded(
                r, mesh, "cosine", k=k, chunk_local=4,
                row_axes=("data", "model"), exclude_self=exclude_self)
        )(rep_sharded)
    dense = dense_similarity(rep, rep, "cosine")
    if exclude_self:
        dense = jnp.where(jnp.eye(u, dtype=bool), -jnp.inf, dense)
    want_vals, want_idx = jax.lax.top_k(dense, k)
    np.testing.assert_allclose(np.sort(np.asarray(vals), 1),
                               np.sort(np.asarray(want_vals), 1),
                               rtol=1e-4, atol=1e-4)
    for i in range(u):
        assert set(np.asarray(idx)[i].tolist()) == set(np.asarray(want_idx)[i].tolist())
    if exclude_self:
        assert not (np.asarray(idx) == np.arange(u)[:, None]).any()


def test_psum_compressed_close_to_exact(mesh):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
    with jax.set_mesh(mesh):
        out = psum_compressed(x, mesh, axis="data")
    exact = x * mesh.shape["data"]  # replicated input summed over the axis
    scale = float(jnp.abs(x).max()) / 127.0
    assert float(jnp.abs(out - exact).max()) <= mesh.shape["data"] * scale + 1e-5


def test_checkpoint_roundtrip_and_resharding(mesh, tmp_path):
    from repro.train.checkpoint import restore_checkpoint, save_checkpoint

    rng = np.random.default_rng(4)
    tree = {
        "w": jax.device_put(
            jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
            NamedSharding(mesh, P("data", "model")),
        ),
        "b": jnp.asarray(rng.normal(size=(8,)).astype(np.float32)),
        "step": jnp.asarray(7, jnp.int32),
    }
    save_checkpoint(tmp_path, 10, tree)
    # restore onto a DIFFERENT sharding (elastic): replicate w
    target = {
        "w": jax.ShapeDtypeStruct((16, 8), jnp.float32),
        "b": jax.ShapeDtypeStruct((8,), jnp.float32),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    shardings = {
        "w": NamedSharding(mesh, P(None, "model")),
        "b": NamedSharding(mesh, P(None)),
        "step": NamedSharding(mesh, P()),
    }
    restored = restore_checkpoint(tmp_path, target, shardings=shardings)
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(tree["w"]))
    np.testing.assert_allclose(np.asarray(restored["b"]), np.asarray(tree["b"]))
    assert int(restored["step"]) == 7
    assert restored["w"].sharding.spec == P(None, "model")


def test_checkpoint_keep_k(tmp_path):
    from repro.train.checkpoint import latest_step, save_checkpoint

    tree = {"x": jnp.ones((4,))}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, s, tree, keep=2)
    import pathlib

    kept = sorted(p.name for p in pathlib.Path(tmp_path).glob("step_*"))
    assert len(kept) == 2 and latest_step(tmp_path) == 5


def test_gnn_shardmap_matches_gspmd_reference(mesh):
    """§Perf H2 variant: explicit-wire message passing == GSPMD reference."""
    from repro.models.gnn import GNNConfig, gnn_forward, gnn_forward_shardmap, init_gnn
    from repro.distributed.sharding import DEFAULT_RULES

    cfg = GNNConfig("g", n_layers=3, d_hidden=16, d_feat=8, n_classes=5)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    N, E = 64, 256
    feats = rng.normal(size=(N, 8)).astype(np.float32)
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    # dst-partition the edges (pipeline contract), pad per owner shard
    srcs, dsts, masks = [], [], []
    per = -(-max((dst // (N // 2) == i).sum() for i in range(2)) // 4) * 4
    for i in range(2):
        sel = dst // (N // 2) == i
        s_, d_ = src[sel], dst[sel]
        pad = per - len(s_)
        srcs.append(np.pad(s_, (0, pad)))
        dsts.append(np.pad(d_, (0, pad), constant_values=i * (N // 2)))
        m = np.zeros(per, np.float32)
        m[: len(s_)] = 1
        masks.append(m)
    src_p, dst_p, mask_p = map(np.concatenate, (srcs, dsts, masks))

    with jax.set_mesh(mesh):
        feats_s = jax.device_put(feats, NamedSharding(mesh, P(("data",), None)))
        e_sh = NamedSharding(mesh, P(("data", "model")))
        out = jax.jit(lambda f, s, d, m: gnn_forward_shardmap(
            params, f, s, d, m, cfg, mesh, N))(
            feats_s, jax.device_put(src_p, e_sh), jax.device_put(dst_p, e_sh),
            jax.device_put(mask_p, e_sh))
    ref = gnn_forward(params, jnp.asarray(feats), jnp.asarray(src_p),
                      jnp.asarray(dst_p), jnp.asarray(mask_p), cfg, DEFAULT_RULES)
    assert float(jnp.abs(out - ref).max()) < 2e-2  # bf16 wire tolerance
