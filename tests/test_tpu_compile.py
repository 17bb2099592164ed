"""Compile-only checks of the main-path Pallas kernels and read programs for
a TPU v5e.

Nothing runs: each kernel is lowered at MovieLens-1M serving widths
(U=6040 users, n=20 landmarks, k=13 neighbours, the registry's
``landmark_cf`` model) and compiled by the TPU compiler for a *described*
v5e chip, which refuses what interpret mode accepts (block shapes that
break the (8, 128) tiling rule, VMEM overruns). A kernel that compiles must
show up in the executable as a ``tpu_custom_call``. The read programs are
compiled at the MovieLens-10M bucket (131,072 x 10,681) for what they hold
in temporaries.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.types import NeighborGraph  # noqa: E402
from repro.kernels.ivf_probe import fused_probe_topk  # noqa: E402
from repro.kernels.knn_topk import (  # noqa: E402
    foldin_topk_kernel, topk_sim_kernel)
from repro.retrieval.index import (  # noqa: E402
    IVFSpec, build_index, resolve_ivf, score_candidates_kernel)
from repro.retrieval.kmeans import assign_clusters_kernel  # noqa: E402

U, N, K = 6040, 20, 13  # ML-1M users x landmark width x neighbours
FOLD_B = 64  # serve.py's default fold-in batch
MEASURES = ("cosine", "pearson", "euclidean")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ivf_layout():
    """(spec, C, cap) of the index ``build_index`` lays out over U rows."""
    spec = resolve_ivf(IVFSpec(), U)
    shapes = jax.eval_shape(lambda r: build_index(r, spec, "cosine"),
                            jax.ShapeDtypeStruct((U, N), jnp.float32))
    return spec, *shapes.lists.shape


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("measure", MEASURES)
def test_topk_sim_kernel_compiles(one_chip, measure):
    rep = _spec((U, N), jnp.float32, one_chip)
    _assert_kernel(
        lambda r: topk_sim_kernel(r, r, k=K, interpret=False,
                                  exclude_self=True, measure=measure), rep)


def test_foldin_topk_kernel_compiles(one_chip):
    q = _spec((FOLD_B, N), jnp.float32, one_chip)
    cand = _spec((8192, N), jnp.float32, one_chip)  # bucket capacity >= U
    _assert_kernel(
        lambda a, b: foldin_topk_kernel(a, b, k=K, interpret=False,
                                        self_offset=U, n_valid=U + FOLD_B),
        q, cand)


def test_assign_clusters_kernel_compiles(one_chip):
    c = resolve_ivf(IVFSpec(), U).n_clusters
    rep = _spec((U, N), jnp.float32, one_chip)
    cent = _spec((c, N), jnp.float32, one_chip)
    _assert_kernel(
        lambda r, m: assign_clusters_kernel(r, m, interpret=False), rep, cent)


@pytest.mark.parametrize("payload", ("f32", "bf16", "int8"))
def test_fused_probe_topk_compiles(one_chip, payload):
    spec, c, cap = _ivf_layout()
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[payload]
    b = 256
    args = [_spec((b, N), jnp.float32, one_chip),
            _spec((b, spec.nprobe), jnp.int32, one_chip),
            _spec((c, cap), jnp.int32, one_chip),
            _spec((c, cap, N), dtype, one_chip),
            _spec((c,), jnp.int32, one_chip),
            _spec((b,), jnp.int32, one_chip)]
    if payload == "int8":
        args.append(_spec((c, cap), jnp.float32, one_chip))

    def probe(q, pr, lists, rows, fill, sids, scale=None):
        return fused_probe_topk(q, pr, lists, rows, scale, fill, k=K,
                                self_ids=sids, interpret=False)

    _assert_kernel(probe, *args)


def test_score_candidates_kernel_compiles(one_chip):
    spec, _, cap = _ivf_layout()
    q = _spec((256, N), jnp.float32, one_chip)
    cand = _spec((256, spec.nprobe * cap, N), jnp.float32, one_chip)
    _assert_kernel(
        lambda a, b: score_candidates_kernel(a, b, interpret=False), q, cand)


@pytest.mark.parametrize("kind", ["pair", "topn"])
def test_read_programs_copy_no_matrix_of_a_lane_aligned_state(one_chip,
                                                              kind):
    """At the ML-10M bucket, both read programs of the mutable serving
    state hold at most their gathered (B, k, P) rows in temporaries: the
    bucketed rating matrix is lane-aligned, so the TPU lays it out
    row-major and a row gather reads only its rows."""
    from repro import mutation
    from repro.core.landmark_cf import LandmarkState
    from repro.lifecycle import buckets

    cap, p, b = 131_072, 10_681, 128
    width = buckets.lane_width(p)
    block = b * K * width * 4  # the (B, k, P) f32 rows
    bst = buckets.BucketedState(
        LandmarkState(_spec((N,), jnp.int32, one_chip),
                      _spec((cap, N), jnp.float32, one_chip),
                      _spec((cap, width), jnp.float32, one_chip),
                      graph=NeighborGraph(
                          _spec((cap, K), jnp.int32, one_chip),
                          _spec((cap, K), jnp.float32, one_chip))),
        _spec((), jnp.int32, one_chip), p)
    mst = mutation.MutableState(bst, _spec((N, p), jnp.float32, one_chip),
                                _spec((cap,), jnp.bool_, one_chip),
                                _spec((cap,), jnp.bool_, one_chip))
    users = _spec((b,), jnp.int32, one_chip)
    if kind == "pair":
        read = jax.jit(mutation.predict_pairs).lower(mst, users, users)
    else:
        read = jax.jit(mutation.recommend_topn,
                       static_argnames="n").lower(mst, users, n=10)
    temp = read.compile().memory_analysis().temp_size_in_bytes
    assert temp <= 1.5 * block, (temp, block)
