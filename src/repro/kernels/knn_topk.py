"""Fused similarity + streaming top-k — the d2/kNN hot path without ever
writing the (U, C) similarity matrix to HBM (§Perf hillclimb, web_fit cell).

Each grid step computes one (bu × bc) sims tile on the MXU, applies the d2
``measure`` epilogue *in-kernel* (VPU, tile-local), and folds the tile into a
running (bu, k) best-list in VMEM via k rounds of max-extract-mask. HBM
traffic drops from O(U·C) sims reads+writes to one pass over the candidate
rows:

  grid = (U/bu, C/bc)  c innermost arbitrary
  VMEM: rep tile (bu, n) + cand tile (bc, n) + best (bu, k) ×2 scratch

Measures (matching ``core.similarity.dense_similarity`` up to dot order):

- ``cosine``    — rows are L2-normalized by the *caller* (one pass, amortized
                  over every tile pair); the tile is the raw dot product.
- ``pearson``   — rows are mean-centered in-kernel (the full feature axis is
                  resident per tile), then cosine of the centered rows.
- ``euclidean`` — squared norms reduced in-kernel, d² = |u|² − 2z + |v|²,
                  epilogue 1/(1+√d²) (``similarity_from_distance``) so the
                  stored weights feed Eq. (1) directly.

The wrapper pads both row axes up to the block multiples (padded candidate
columns are masked to -inf via ``n_valid``), and ``exclude_self`` masks the
global diagonal in-kernel — so the kernel serves every d2 graph build
(core.graph backend="pallas") where rep == cand and row u must not pick
itself.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-8
MEASURES = ("cosine", "pearson", "euclidean")


def _tile_sims(rep, cand, measure):
    """One (bu, bc) d2 tile with the measure epilogue applied in-kernel.

    ``rep``/``cand`` are f32 tiles carrying the FULL feature axis, so
    row-local reductions (means, squared norms) are exact per tile."""
    if measure == "pearson":
        rep = rep - jnp.mean(rep, axis=1, keepdims=True)
        cand = cand - jnp.mean(cand, axis=1, keepdims=True)
    # HIGHEST, as dense_similarity: the graph's weights are f32 similarities,
    # not whatever pass count the MXU defaults to for f32 operands
    z = jax.lax.dot_general(rep, cand, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (bu, bc)
    if measure == "cosine":  # caller pre-normalizes rows
        return z
    nu = jnp.sum(rep * rep, axis=1, keepdims=True)  # (bu, 1)
    nv = jnp.sum(cand * cand, axis=1)[None, :]  # (1, bc)
    if measure == "pearson":
        return z / jnp.maximum(jnp.sqrt(nu) * jnp.sqrt(nv), EPS)
    if measure == "euclidean":
        d2 = jnp.maximum(nu - 2.0 * z + nv, 0.0)
        return 1.0 / (1.0 + jnp.sqrt(d2))
    raise ValueError(f"unknown measure {measure!r}")


# the d2 tile + epilogue is the shared building block of every in-kernel
# similarity consumer; the IVF quantizer's assignment kernel
# (repro.retrieval.kmeans) reuses it under this public name
tile_sims = _tile_sims


def _kernel(rep_ref, cand_ref, val_ref, idx_ref, best_v, best_i, *, k, n_c, bc,
            bu, n_valid, exclude_self, measure):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        best_v[...] = jnp.full_like(best_v, -jnp.inf)
        best_i[...] = jnp.zeros_like(best_i)

    rep = rep_ref[...].astype(jnp.float32)  # (bu, n)
    cand = cand_ref[...].astype(jnp.float32)  # (bc, n)
    sims = _tile_sims(rep, cand, measure)  # (bu, bc)
    base = pl.program_id(1) * bc
    # global candidate / query row ids for this tile (2D iota: TPU-safe)
    col_gid = base + jax.lax.broadcasted_iota(jnp.int32, (bu, bc), 1)
    invalid = col_gid >= n_valid
    if exclude_self:
        row_gid = pl.program_id(0) * bu + jax.lax.broadcasted_iota(
            jnp.int32, (bu, bc), 0)
        invalid = invalid | (col_gid == row_gid)
    sims = jnp.where(invalid, -jnp.inf, sims)

    bv, bi = best_v[...], best_i[...]
    for _ in range(k):  # k rounds: extract tile max, displace the current min
        col = jnp.argmax(sims, axis=1)
        m = jnp.max(sims, axis=1)
        jmin = jnp.argmin(bv, axis=1)
        vmin = jnp.min(bv, axis=1)
        take = m > vmin
        bv = jnp.where(
            take[:, None] & (jnp.arange(bv.shape[1])[None] == jmin[:, None]),
            m[:, None], bv,
        )
        bi = jnp.where(
            take[:, None] & (jnp.arange(bi.shape[1])[None] == jmin[:, None]),
            (base + col)[:, None].astype(jnp.int32), bi,
        )
        sims = jnp.where(jnp.arange(sims.shape[1])[None] == col[:, None], -jnp.inf, sims)
    best_v[...], best_i[...] = bv, bi

    @pl.when(pl.program_id(1) == n_c - 1)
    def _done():
        val_ref[...] = best_v[...]
        idx_ref[...] = best_i[...]


def topk_sim_kernel(
    rep: jax.Array,  # (U, n) query rows (L2-normalized for cosine)
    cand: jax.Array,  # (C, n) candidate rows
    k: int = 14,
    block: Tuple[int, int] = (128, 512),
    interpret: bool = None,
    exclude_self: bool = False,
    n_valid: Optional[int] = None,
    measure: str = "cosine",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (vals, idx): for every rep row, top-k candidate d2 weights.

    Shapes need not be block multiples — both row axes are zero-padded up to
    them and padded candidates are masked out (never selected). ``n_valid``
    restricts selection to the first ``n_valid`` candidate rows (defaults to
    ``cand.shape[0]``). ``exclude_self`` assumes rep and cand are the *same*
    row set (rep row i == cand row i) and masks the diagonal; slots that end
    up empty (e.g. fully masked tiles) come back as -inf values. ``measure``
    selects the in-kernel epilogue (module docstring); cosine expects
    pre-normalized rows, pearson/euclidean take raw representation rows.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    u, n = rep.shape
    c = cand.shape[0]
    if n_valid is None:
        n_valid = c
    bu, bc = block
    bu, bc = min(bu, -(-u // 8) * 8), min(bc, -(-c // 8) * 8)
    u_pad, c_pad = -(-u // bu) * bu, -(-c // bc) * bc
    if u_pad != u:
        rep = jnp.pad(rep, ((0, u_pad - u), (0, 0)))
    if c_pad != c:
        cand = jnp.pad(cand, ((0, c_pad - c), (0, 0)))
    n_c = c_pad // bc

    from jax.experimental.pallas import tpu as pltpu

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    vals, idx = pl.pallas_call(
        functools.partial(_kernel, k=k, n_c=n_c, bc=bc, bu=bu,
                          n_valid=n_valid, exclude_self=exclude_self,
                          measure=measure),
        grid=(u_pad // bu, n_c),
        in_specs=[
            pl.BlockSpec((bu, n), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, n), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bu, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bu, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((u_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((u_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bu, k), jnp.float32),
            pltpu.VMEM((bu, k), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(rep, cand)
    return vals[:u], idx[:u]


def topk_sim_ref(rep, cand, k=14):
    """Oracle: dense sims + lax.top_k."""
    sims = rep.astype(jnp.float32) @ cand.astype(jnp.float32).T
    return jax.lax.top_k(sims, k)


# --------------------------------------------------------------------- fold-in
# Serving variant for the skinny (b, C) shape, b ≪ C: the whole query block
# lives in VMEM for the kernel's entire lifetime and the grid runs over
# candidate chunks only. The square-tile kernel above re-fetches its rep tile
# every (i, j) step and pays a (bu=128)-row tile even when b=64; here the
# query fetch happens once and the row axis is exactly the padded batch.


def _foldin_kernel(rep_ref, cand_ref, val_ref, idx_ref, best_v, best_i, *,
                   k, n_c, bc, n_valid, self_offset, measure):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        best_v[...] = jnp.full_like(best_v, -jnp.inf)
        best_i[...] = jnp.zeros_like(best_i)

    rep = rep_ref[...].astype(jnp.float32)  # (b_pad, n) — resident all steps
    cand = cand_ref[...].astype(jnp.float32)  # (bc, n)
    sims = _tile_sims(rep, cand, measure)  # (b_pad, bc)
    b_pad = rep.shape[0]
    base = pl.program_id(0) * bc
    col_gid = base + jax.lax.broadcasted_iota(jnp.int32, (b_pad, bc), 1)
    # query row i is candidate row self_offset + i (its own fold-in slot)
    row_gid = self_offset + jax.lax.broadcasted_iota(jnp.int32, (b_pad, bc), 0)
    sims = jnp.where((col_gid >= n_valid) | (col_gid == row_gid), -jnp.inf, sims)

    bv, bi = best_v[...], best_i[...]
    for _ in range(k):  # k rounds: extract chunk max, displace the current min
        col = jnp.argmax(sims, axis=1)
        m = jnp.max(sims, axis=1)
        jmin = jnp.argmin(bv, axis=1)
        vmin = jnp.min(bv, axis=1)
        take = m > vmin
        hit = take[:, None] & (jnp.arange(bv.shape[1])[None] == jmin[:, None])
        bv = jnp.where(hit, m[:, None], bv)
        bi = jnp.where(hit, (base + col)[:, None].astype(jnp.int32), bi)
        sims = jnp.where(jnp.arange(sims.shape[1])[None] == col[:, None],
                         -jnp.inf, sims)
    best_v[...], best_i[...] = bv, bi

    @pl.when(pl.program_id(0) == n_c - 1)
    def _done():
        val_ref[...] = best_v[...]
        idx_ref[...] = best_i[...]


def foldin_topk_kernel(
    rep: jax.Array,  # (b, n) fold-in query rows (L2-normalized for cosine)
    cand: jax.Array,  # (C, n) candidate rows (existing + new rows)
    k: int = 14,
    block_c: int = 512,
    interpret: bool = None,
    self_offset: Optional[int] = None,
    n_valid: Optional[int] = None,
    measure: str = "cosine",
) -> Tuple[jax.Array, jax.Array]:
    """Top-k candidate d2 weights for a skinny fold-in batch.

    ``self_offset`` marks where the query rows sit in the candidate id space
    (query i == candidate ``self_offset + i``, masked out so a fold-in row
    never lists itself); pass None (→ past the end) when queries are not
    among the candidates. ``n_valid`` restricts selection to the first
    ``n_valid`` candidates, and ``measure`` selects the in-kernel epilogue,
    as in :func:`topk_sim_kernel`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n = rep.shape
    c = cand.shape[0]
    if n_valid is None:
        n_valid = c
    if self_offset is None:
        self_offset = c  # no candidate id ever matches
    b_pad = -(-b // 8) * 8
    bc = min(block_c, -(-c // 8) * 8)
    c_pad = -(-c // bc) * bc
    if b_pad != b:
        rep = jnp.pad(rep, ((0, b_pad - b), (0, 0)))
    if c_pad != c:
        cand = jnp.pad(cand, ((0, c_pad - c), (0, 0)))
    n_c = c_pad // bc

    from jax.experimental.pallas import tpu as pltpu

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    vals, idx = pl.pallas_call(
        functools.partial(_foldin_kernel, k=k, n_c=n_c, bc=bc,
                          n_valid=n_valid, self_offset=self_offset,
                          measure=measure),
        grid=(n_c,),
        in_specs=[
            pl.BlockSpec((b_pad, n), lambda j: (0, 0)),  # fetched once
            pl.BlockSpec((bc, n), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b_pad, k), lambda j: (0, 0)),
            pl.BlockSpec((b_pad, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b_pad, k), jnp.float32),
            pltpu.VMEM((b_pad, k), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(rep, cand)
    return vals[:b], idx[:b]
