"""Fused IVF probe — gather + d2 score + top-k in one VMEM-resident pass.

The slice+GEMM probe path in ``repro.retrieval.index.search`` gathers every
probed posting list into a ``(qb, nprobe*cap, n)`` candidate tensor, scores
it, and re-ranks — three HBM round-trips of the candidate set per query
block. At the million-user mark that tensor IS the serving cost: the rows
are read once to build it, once to score it, and the scores once more to
rank them. This kernel removes all three: for each (query, probe-rank) grid
step it DMAs exactly one posting list's block into VMEM — the probed cell id
comes from a scalar-prefetched probe table, so the gather is expressed as a
data-dependent ``BlockSpec`` index_map, not a materialized gather — scores
it with the exact ``dense_similarity`` algebra, and folds it into a (1, k)
running best-list held in VMEM scratch. HBM sees one sequential pass over
the probed rows and a (b, k) result, nothing else.

  grid = (b, nprobe)            probe rank innermost, arbitrary
  scalar prefetch: probe (b, nprobe), fill (C,), self ids (b,),
                   probe_ok (b, nprobe)
  VMEM: query row (1, 1, n) + posting block (1, cap, n) [+ scale (1, cap, 1)]
        + best (1, k) ×2 scratch

Exactness: scores use the same HIGHEST-precision dot + measure epilogue as
``core.similarity.dense_similarity`` (not ``knn_topk._tile_sims``, whose
cosine expects caller-normalized rows), and the best-list insert breaks
value ties by *lower candidate id* — the canonical (weight desc, id asc)
order every streaming scan in ``core.graph`` produces. At full probe the
candidate set is the whole index, so the result is bit-identical to the
exact slice+GEMM path (and hence to ``backend="streaming"``); acceptance-
tested in tests/test_ivf_fused.py on all three measures. The positional
tie-break of ``lax.top_k`` never appears here, which is what lets the
kernel visit cells in any probe order.

Quantized payloads (``IVFIndex.payload_dtype``) dequantize in-kernel after
the block DMA: bf16/int8 shrink the HBM read 2–4x, and the f32 compute path
is untouched (int8 blocks ride with a (1, cap, 1) f32 scale block).

The probe table must hold *distinct* cells per query (``lax.top_k`` over
centroid sims guarantees it); a repeated cell would insert its members
twice. ``probe_ok`` masks individual (query, rank) slots — the sharded
router (``retrieval.sharded``) uses it to skip cells a shard does not own
while keeping the grid static.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.similarity import EPS

INT_MAX = jnp.iinfo(jnp.int32).max


def _probe_sims(q, cand, measure):
    """(1, cap) d2 scores of one query against one posting block.

    Bit-for-bit the ``core.similarity.dense_similarity`` algebra (HIGHEST
    precision dot, same epilogue operation order) phrased on a (1, n) ×
    (cap, n) tile — full probe parity with the GEMM path rests on this."""
    if measure == "pearson":
        q = q - q.mean(axis=-1, keepdims=True)
        cand = cand - cand.mean(axis=-1, keepdims=True)
    z = jax.lax.dot_general(q, cand, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (1, cap)
    if measure in ("cosine", "pearson"):
        nu = jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True))
        nv = jnp.sqrt(jnp.sum(cand * cand, axis=-1))[None, :]
        return z / jnp.maximum(nu * nv, EPS)
    if measure == "euclidean":
        nu = jnp.sum(q * q, axis=-1, keepdims=True)
        nv = jnp.sum(cand * cand, axis=-1)[None, :]
        return 1.0 / (1.0 + jnp.sqrt(jnp.maximum(nu - 2.0 * z + nv, 0.0)))
    raise ValueError(f"unknown measure {measure!r}")


def _first(mask, iota):
    """(1, 1) index of the first set slot of a (1, w) mask (one is always set).

    A min over the lane iota: Mosaic lowers argmax for f32 operands only."""
    return jnp.min(jnp.where(mask, iota, INT_MAX), axis=1, keepdims=True)


def _kernel(probe_ref, fill_ref, sids_ref, ok_ref, q_ref, lists_ref, rows_ref,
            *rest, k, nprobe, cap, measure, has_scale):
    if has_scale:
        scale_ref, val_ref, idx_ref, best_v, best_i = rest
    else:
        val_ref, idx_ref, best_v, best_i = rest
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_v[...] = jnp.full_like(best_v, -jnp.inf)
        best_i[...] = jnp.full_like(best_i, INT_MAX)

    q = q_ref[0].astype(jnp.float32)  # (1, n)
    cand = rows_ref[0].astype(jnp.float32)  # (cap, n) — dequantize post-DMA
    if has_scale:
        cand = cand * scale_ref[0]  # (cap, 1) column of row scales
    sims = _probe_sims(q, cand, measure)  # (1, cap)
    ids = lists_ref[0]  # (1, cap)
    cell = probe_ref[i, j]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    keep = (slot < fill_ref[cell]) & (ids != sids_ref[i]) & (ok_ref[i, j] != 0)
    # masked slots carry (-inf, INT_MAX): lexicographically below every live
    # candidate AND every init best-list entry, so they can never displace
    sims = jnp.where(keep, sims, -jnp.inf)
    ids = jnp.where(keep, ids, INT_MAX)

    bv, bi = best_v[...], best_i[...]  # (1, k)
    kio = jax.lax.broadcasted_iota(jnp.int32, bv.shape, 1)
    cio = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    for _ in range(k):  # k rounds: lexicographic extract-max, displace worst
        m = jnp.max(sims, axis=1, keepdims=True)  # (1, 1)
        tie = sims == m
        sel = jnp.min(jnp.where(tie, ids, INT_MAX), axis=1, keepdims=True)
        vmin = jnp.min(bv, axis=1, keepdims=True)
        wtie = bv == vmin
        wid = jnp.max(jnp.where(wtie, bi, jnp.iinfo(jnp.int32).min),
                      axis=1, keepdims=True)  # worst = (min value, max id)
        take = (m > vmin) | ((m == vmin) & (sel < wid))  # (1, 1)
        # first slot holding the worst entry, so duplicate (-inf, INT_MAX)
        # init entries are displaced one at a time
        hit = take & (kio == _first(wtie & (bi == wid), kio))
        bv = jnp.where(hit, m, bv)
        bi = jnp.where(hit, sel, bi)
        drop = cio == _first(tie & (ids == sel), cio)
        sims = jnp.where(drop, -jnp.inf, sims)
        ids = jnp.where(drop, INT_MAX, ids)
    best_v[...], best_i[...] = bv, bi

    @pl.when(j == nprobe - 1)
    def _done():
        val_ref[0] = best_v[...]
        idx_ref[0] = best_i[...]


def fused_probe_topk(
    q: jax.Array,  # (b, n) f32 query rows
    probe: jax.Array,  # (b, nprobe) int32 probed cells, distinct per query
    lists: jax.Array,  # (C, cap) int32 posting-list ids
    rows: jax.Array,  # (C, cap, n) payload rows (f32|bf16|int8)
    scale: Optional[jax.Array],  # (C, cap) f32 int8 scales, or None
    fill: jax.Array,  # (C,) int32
    *,
    k: int,
    measure: str = "cosine",
    self_ids: Optional[jax.Array] = None,  # (b,) id to exclude, -1 = none
    probe_ok: Optional[jax.Array] = None,  # (b, nprobe) bool; False = skip
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k (vals, ids) per query over its probed posting lists, fused.

    Returns results in the canonical (value desc, id asc) order; empty slots
    are (-inf, 0), matching ``search``'s documented contract. See module
    docstring for the exactness and distinct-probe requirements.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n = q.shape
    nprobe = probe.shape[1]
    c, cap = lists.shape
    if self_ids is None:
        self_ids = jnp.full((b,), -1, jnp.int32)
    ok = (jnp.ones((b, nprobe), jnp.int32) if probe_ok is None
          else probe_ok.astype(jnp.int32))
    has_scale = scale is not None

    from jax.experimental.pallas import tpu as pltpu

    # Every operand carries a unit axis ahead of its last two, so each block's
    # last two dims equal the array's — the TPU tiling rule that a (1, n)
    # block of a (b, n) array breaks.
    row = lambda i, j, p, f, s, o: (i, 0, 0)  # noqa: E731
    cell = lambda i, j, p, f, s, o: (p[i, j], 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, n), row),
        pl.BlockSpec((1, 1, cap), cell),
        pl.BlockSpec((1, cap, n), cell),
    ]
    inputs = [q.astype(jnp.float32)[:, None, :],
              lists.astype(jnp.int32)[:, None, :], rows]
    if has_scale:
        in_specs.append(pl.BlockSpec((1, cap, 1), cell))
        inputs.append(scale[:, :, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nprobe),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, k), row), pl.BlockSpec((1, 1, k), row)],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    vals, ids = pl.pallas_call(
        functools.partial(_kernel, k=k, nprobe=nprobe, cap=cap,
                          measure=measure, has_scale=has_scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(probe.astype(jnp.int32), fill.astype(jnp.int32),
      self_ids.astype(jnp.int32), ok, *inputs)
    vals, ids = vals[:, 0], ids[:, 0]
    # canonicalize slot order: two stable argsorts -> (value desc, id asc),
    # the same normalization extend_neighbor_graph_sharded applies to merged
    # lists. -inf slots (id INT_MAX) sink to the tail; surface them as
    # (-inf, 0) per the search contract.
    o1 = jnp.argsort(ids, axis=1)
    v1 = jnp.take_along_axis(vals, o1, axis=1)
    i1 = jnp.take_along_axis(ids, o1, axis=1)
    sel = jnp.argsort(-v1, axis=1)
    vals = jnp.take_along_axis(v1, sel, axis=1)
    ids = jnp.take_along_axis(i1, sel, axis=1)
    return vals, jnp.where(jnp.isneginf(vals), 0, ids)
