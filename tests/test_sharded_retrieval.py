"""Sharded IVF vs the single-device index — the shadow-replica pattern of
test_sharded_serving applied to retrieval: every sharded operation is run
against its single-device counterpart on identical inputs, and the full-probe
search must be *bit-identical* (canonical merge == canonical top-k).
"""
import os

import pytest

# needs >1 device; spawn-style env var must be set before jax init.
if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.graph import finalize_topk  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.retrieval.index import (  # noqa: E402
    IVFSpec, append, build_index, ensure_index_capacity, recall_at_k, search,
    search_early_exit)
from repro.retrieval.sharded import (  # noqa: E402
    append_sharded, build_index_sharded, ensure_index_capacity_sharded,
    resolve_ivf_sharded, search_early_exit_sharded, search_sharded,
    shard_index)

pytestmark = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs 8 host devices")

AXES = ("pod", "data")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((2, 4), AXES)


def _mk(u=300, n=16, seed=0, measure="cosine", payload_dtype="f32"):
    rep = jax.random.normal(jax.random.PRNGKey(seed), (u, n))
    spec = resolve_ivf_sharded(IVFSpec(payload_dtype=payload_dtype), u, 8)
    return rep, spec, build_index(rep, spec, measure)


def _graphs(vals, ids):
    g = finalize_topk(vals, ids)
    return np.asarray(g.weights), np.asarray(g.indices)


def test_resolve_rounds_cells_to_shard_multiple():
    spec = resolve_ivf_sharded(IVFSpec(), 300, 8)
    assert spec.n_clusters % 8 == 0
    assert spec.nprobe <= spec.n_clusters
    assert spec.spill_choices == spec.n_clusters


@pytest.mark.parametrize("measure", ("cosine", "pearson", "euclidean"))
def test_full_probe_sharded_bitwise_equals_single_device(mesh, measure):
    rep, spec, index = _mk(measure=measure)
    sidx = shard_index(index, mesh, AXES)
    q = rep[:40]
    sid = jnp.arange(40, dtype=jnp.int32)
    c = spec.n_clusters
    vr, ir = search(index, q, 9, c, measure, self_ids=sid, scorer="jnp")
    vs, is_, probed = search_sharded(sidx, q, 9, c, mesh, AXES, measure,
                                     self_ids=sid)
    wr, nr = _graphs(vr, ir)
    ws, ns = _graphs(vs, is_)
    np.testing.assert_array_equal(nr, ns)
    np.testing.assert_array_equal(wr, ws)
    # full probe touches every cell exactly once across the mesh
    np.testing.assert_array_equal(np.asarray(probed), np.full(40, c))


def test_sharded_partial_probe_recall_and_routing(mesh):
    rep, spec, index = _mk(u=400)
    sidx = shard_index(index, mesh, AXES)
    q = rep[:32]
    sid = jnp.arange(32, dtype=jnp.int32)
    c = spec.n_clusters
    vx, ix = search(index, q, 9, c, "cosine", self_ids=sid)
    vs, is_, probed = search_sharded(sidx, q, 9, spec.nprobe, mesh, AXES,
                                     self_ids=sid)
    # the sharded router probes the same cells the single-device top_k picks
    assert float(recall_at_k(is_, ix, vs, vx)) >= 0.6
    np.testing.assert_array_equal(np.asarray(probed),
                                  np.full(32, spec.nprobe))
    # a local budget bounds the per-shard work; probed never exceeds it × S
    _, _, probed_b = search_sharded(sidx, q, 9, spec.nprobe, mesh, AXES,
                                    self_ids=sid, local_budget=2)
    assert int(np.max(np.asarray(probed_b))) <= 2 * 8


def test_append_sharded_bitwise_equals_single_device(mesh):
    rep, spec, index = _mk(u=280)
    sidx = shard_index(index, mesh, AXES)
    batch = jax.random.normal(jax.random.PRNGKey(7), (24, 16))
    ids = 280 + jnp.arange(24, dtype=jnp.int32)
    ref = append(index, batch, ids, "cosine")
    got = append_sharded(sidx, batch, ids, mesh, AXES, "cosine")
    for name in ("lists", "rows", "fill"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, name)),
                                      np.asarray(getattr(got, name)),
                                      err_msg=name)


def test_append_sharded_masked_batch(mesh):
    rep, spec, index = _mk(u=280)
    sidx = shard_index(index, mesh, AXES)
    batch = jax.random.normal(jax.random.PRNGKey(8), (16, 16))
    ids = 280 + jnp.arange(16, dtype=jnp.int32)
    ref = append(index, batch, ids, "cosine", b_valid=jnp.int32(5))
    got = append_sharded(sidx, batch, ids, mesh, AXES, "cosine",
                         b_valid=jnp.int32(5))
    np.testing.assert_array_equal(np.asarray(ref.fill), np.asarray(got.fill))
    assert int(np.asarray(got.fill).sum()) == 280 + 5


def test_capacity_growth_sharded_preserves_search(mesh):
    rep, spec, index = _mk(u=200)
    sidx = shard_index(index, mesh, AXES)
    grown, grew = ensure_index_capacity_sharded(
        sidx, int(sidx.capacity * 2), mesh, AXES)
    assert grew and grown.capacity > sidx.capacity
    q = rep[:16]
    c = spec.n_clusters
    v0, i0, _ = search_sharded(sidx, q, 7, c, mesh, AXES)
    v1, i1, _ = search_sharded(grown, q, 7, c, mesh, AXES)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    # single-device growth on the same geometry agrees bitwise
    ref, ref_grew = ensure_index_capacity(index, int(index.capacity * 2))
    assert ref_grew and ref.capacity == grown.capacity
    np.testing.assert_array_equal(np.asarray(ref.lists),
                                  np.asarray(grown.lists))


def test_sharded_int8_payload_round_trip(mesh):
    rep, spec, index = _mk(u=260, payload_dtype="int8")
    assert index.scale is not None
    sidx = shard_index(index, mesh, AXES)
    batch = jax.random.normal(jax.random.PRNGKey(9), (16, 16))
    ids = 260 + jnp.arange(16, dtype=jnp.int32)
    ref = append(index, batch, ids, "cosine")
    got = append_sharded(sidx, batch, ids, mesh, AXES, "cosine")
    np.testing.assert_array_equal(np.asarray(ref.rows), np.asarray(got.rows))
    np.testing.assert_array_equal(np.asarray(ref.scale),
                                  np.asarray(got.scale))
    # full-probe search on the quantized sharded index == single-device
    q = rep[:20]
    c = spec.n_clusters
    vr, ir = search(ref, q, 9, c, "cosine")
    vs, is_, _ = search_sharded(got, q, 9, c, mesh, AXES)
    wr, nr = _graphs(vr, ir)
    ws, ns = _graphs(vs, is_)
    np.testing.assert_array_equal(nr, ns)
    np.testing.assert_array_equal(wr, ws)


def test_build_index_sharded_matches_host_build(mesh):
    rep = jax.random.normal(jax.random.PRNGKey(4), (240, 12))
    spec = resolve_ivf_sharded(IVFSpec(), 240, 8)
    a = build_index(rep, spec, "cosine")
    b = build_index_sharded(rep, spec, mesh, AXES, "cosine")
    for name in ("centroids", "lists", "rows", "fill"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


# -------------------------------------------------------- sharded early exit


def test_early_exit_sharded_full_probe_bitwise(mesh):
    """With patience past the probe count no query can retire early: the
    sharded early-exit search must equal the single-device early-exit
    bit-for-bit, probing every cell exactly once (the per-query psum'd
    probe count is the proof)."""
    rep, spec, index = _mk()
    sidx = shard_index(index, mesh, AXES)
    q = rep[:40]
    sid = jnp.arange(40, dtype=jnp.int32)
    c = spec.n_clusters
    vr, ir, pr = search_early_exit(index, q, 9, c, "cosine", self_ids=sid,
                                   patience=c + 1)
    vs, is_, ps = search_early_exit_sharded(sidx, q, 9, c, mesh, AXES,
                                            "cosine", self_ids=sid,
                                            patience=c + 1)
    wr, nr = _graphs(vr, ir)
    ws, ns = _graphs(vs, is_)
    np.testing.assert_array_equal(nr, ns)
    np.testing.assert_array_equal(wr, ws)
    np.testing.assert_array_equal(np.asarray(ps), np.full(40, c))
    np.testing.assert_array_equal(np.asarray(pr), np.asarray(ps))


def test_early_exit_sharded_reduces_probing_keeps_recall(mesh):
    """Stability only advances on locally-scored cells, so exits need more
    than ``patience`` cells per shard: C=64 over 8 shards gives each shard
    8 — enough for patience=2 to retire queries before the budget."""
    rep = jax.random.normal(jax.random.PRNGKey(0), (300, 16))
    spec = resolve_ivf_sharded(IVFSpec(n_clusters=64), 300, 8)
    index = build_index(rep, spec, "cosine")
    sidx = shard_index(index, mesh, AXES)
    q = rep[:40]
    sid = jnp.arange(40, dtype=jnp.int32)
    c = spec.n_clusters
    ve, ie = search(index, q, 9, c, "cosine", self_ids=sid)  # exact ref
    va, ia, probed = search_early_exit_sharded(sidx, q, 9, c, mesh, AXES,
                                               "cosine", self_ids=sid,
                                               patience=2)
    assert float(np.mean(np.asarray(probed))) < c, \
        "patience=2 at full probe budget retired no query early"
    assert float(recall_at_k(ia, ie, va, ve)) >= 0.6
    # looser patience can only probe more
    _, _, probed4 = search_early_exit_sharded(sidx, q, 9, c, mesh, AXES,
                                              "cosine", self_ids=sid,
                                              patience=4)
    assert (np.asarray(probed) <= np.asarray(probed4)).all()


def test_early_exit_sharded_local_budget_caps_per_shard_work(mesh):
    """At partial probe each shard scans at most ``local_budget`` ranks (a
    full probe instead forces the exact per-shard budget ``C/S``, so the
    cap is only meaningful when nprobe < n_clusters)."""
    rep, spec, index = _mk()
    sidx = shard_index(index, mesh, AXES)
    q = rep[:40]
    sid = jnp.arange(40, dtype=jnp.int32)
    nprobe = spec.n_clusters - 8
    _, _, probed = search_early_exit_sharded(sidx, q, 9, nprobe,
                                             mesh, AXES, "cosine",
                                             self_ids=sid, patience=99,
                                             local_budget=2)
    assert int(np.max(np.asarray(probed))) <= 2 * 8, \
        "a shard probed past its local budget"
