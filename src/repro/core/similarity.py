"""Masked co-rated similarity measures as fused matrix products.

This is the TPU-native re-expression of the paper's Algorithms 2 and 4
(scalar triple loops over co-rated items). Every measure decomposes into six
shared contractions over the item axis (DESIGN.md §2):

    z  = (R)(R_L)ᵀ         co-rated dot products          (R has 0 at missing)
    x  = (R²) M_Lᵀ         Σ r_uv² over the co-rated set
    y  = M (R_L²)ᵀ         Σ r_lv² over the co-rated set
    c  = M M_Lᵀ            co-rated counts
    sx = R M_Lᵀ            Σ r_uv  over the co-rated set   (Pearson)
    sy = M R_Lᵀ            Σ r_lv  over the co-rated set   (Pearson)

(the ⊙M masks are implicit because missing entries are stored as 0).

These jnp implementations are also the oracles for the fused Pallas kernel in
``repro/kernels/masked_similarity.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-8
MEASURES = ("cosine", "pearson", "euclidean")


def corated_moments(
    r_a: jax.Array, r_b: jax.Array, precision=jax.lax.Precision.HIGHEST
) -> Tuple[jax.Array, ...]:
    """Six co-rated moment matrices between user blocks ``r_a (A,P)``, ``r_b (B,P)``."""
    m_a = (r_a != 0).astype(r_a.dtype)
    m_b = (r_b != 0).astype(r_b.dtype)
    dot = partial(jnp.matmul, precision=precision)
    z = dot(r_a, r_b.T)
    x = dot(r_a * r_a, m_b.T)
    y = dot(m_a, (r_b * r_b).T)
    c = dot(m_a, m_b.T)
    sx = dot(r_a, m_b.T)
    sy = dot(m_a, r_b.T)
    return z, x, y, c, sx, sy


def _finalize(measure: str, z, x, y, c, sx, sy) -> jax.Array:
    """Apply the measure epilogue. Pairs with <2 co-rated items get 0 (paper Alg. 2)."""
    valid = c > 1
    if measure == "cosine":
        sim = z / jnp.maximum(jnp.sqrt(x) * jnp.sqrt(y), EPS)
    elif measure == "pearson":
        cc = jnp.maximum(c, 1.0)
        cov = z - sx * sy / cc
        var_a = jnp.maximum(x - sx * sx / cc, 0.0)
        var_b = jnp.maximum(y - sy * sy / cc, 0.0)
        sim = cov / jnp.maximum(jnp.sqrt(var_a) * jnp.sqrt(var_b), EPS)
    elif measure == "euclidean":
        # distance over the co-rated set; see similarity_from_distance for d2 use.
        sim = jnp.sqrt(jnp.maximum(x - 2.0 * z + y, 0.0))
    else:
        raise ValueError(f"unknown measure {measure!r}")
    return jnp.where(valid, sim, 0.0)


@partial(jax.jit, static_argnames=("measure",))
def masked_similarity(r_a: jax.Array, r_b: jax.Array, measure: str = "cosine") -> jax.Array:
    """Pairwise similarity between rows of two rating blocks over co-rated items.

    This is ``d1`` of the paper (Algorithm 2 for cosine). ``r_b`` is typically
    the landmark block ``(n, P)``. Returns ``(A, B)``.
    """
    return _finalize(measure, *corated_moments(r_a, r_b))


def similarity_from_distance(dist: jax.Array) -> jax.Array:
    """Decreasing positive transform so Euclidean can weight Eq. 1 (DESIGN.md §8)."""
    return 1.0 / (1.0 + dist)


@partial(jax.jit, static_argnames=("measure",))
def dense_similarity(u: jax.Array, v: jax.Array, measure: str = "cosine") -> jax.Array:
    """Similarity between *dense* landmark-space vectors (paper Algorithm 4, d2).

    Unlike d1 there is no co-rated masking: every user has all ``n`` landmark
    coordinates. Plain GEMM + epilogue — MXU-friendly.
    """
    precision = jax.lax.Precision.HIGHEST
    if measure == "cosine":
        z = jnp.matmul(u, v.T, precision=precision)
        nu = jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True))
        nv = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
        return z / jnp.maximum(nu * nv.T, EPS)
    if measure == "pearson":
        uc = u - u.mean(axis=-1, keepdims=True)
        vc = v - v.mean(axis=-1, keepdims=True)
        z = jnp.matmul(uc, vc.T, precision=precision)
        nu = jnp.sqrt(jnp.sum(uc * uc, axis=-1, keepdims=True))
        nv = jnp.sqrt(jnp.sum(vc * vc, axis=-1, keepdims=True))
        return z / jnp.maximum(nu * nv.T, EPS)
    if measure == "euclidean":
        sq_u = jnp.sum(u * u, axis=-1, keepdims=True)
        sq_v = jnp.sum(v * v, axis=-1, keepdims=True)
        d2 = sq_u - 2.0 * jnp.matmul(u, v.T, precision=precision) + sq_v.T
        return similarity_from_distance(jnp.sqrt(jnp.maximum(d2, 0.0)))
    raise ValueError(f"unknown measure {measure!r}")


@partial(jax.jit, static_argnames=("measure",))
def full_similarity_matrix(ratings: jax.Array, measure: str = "cosine") -> jax.Array:
    """Baseline (paper Algorithm 1): all-pairs similarity over co-rated items.

    O(|U|²·|P|) — the cost the landmark method removes. Euclidean is converted
    to a similarity so it can weight Eq. 1 directly (validity tracked via the
    co-rated count, not the distance value: distance 0 is a perfect match).
    """
    z, x, y, c, sx, sy = corated_moments(ratings, ratings)
    s = _finalize(measure, z, x, y, c, sx, sy)
    if measure == "euclidean":
        s = jnp.where(c > 1, similarity_from_distance(s), 0.0)
    return s


@partial(jax.jit, static_argnames=("measure", "chunk"))
def blocked_masked_similarity(
    r: jax.Array, landmarks: jax.Array, measure: str = "cosine", chunk: int = 4096
) -> jax.Array:
    """d1 with the Pallas kernel's schedule in pure JAX: stream item chunks,
    carry the six (U, n) moment accumulators. Bounds temporaries to one
    (U, chunk) tile regardless of |P| — the pod-scale path (web_fit).
    All ops are row-local, so a user-sharded ``r`` never reshards."""
    u, p = r.shape
    n_chunks = -(-p // chunk)
    pad = n_chunks * chunk - p
    if pad:
        r = jnp.pad(r, ((0, 0), (0, pad)))
        landmarks = jnp.pad(landmarks, ((0, 0), (0, pad)))

    def body(carry, c_idx):
        z, x, y, c, sx, sy = carry
        ra = jax.lax.dynamic_slice_in_dim(r, c_idx * chunk, chunk, axis=1)
        rb = jax.lax.dynamic_slice_in_dim(landmarks, c_idx * chunk, chunk, axis=1)
        dz, dx, dy, dc, dsx, dsy = corated_moments(ra, rb, jax.lax.Precision.DEFAULT)
        return (z + dz, x + dx, y + dy, c + dc, sx + dsx, sy + dsy), None

    n_lm = landmarks.shape[0]
    init = tuple(jnp.zeros((u, n_lm), jnp.float32) for _ in range(6))
    (z, x, y, c, sx, sy), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return _finalize(measure, z, x, y, c, sx, sy)


def streaming_knn_graph(  # callers jit this; ``rules`` stays a static python dict
    rep: jax.Array, measure: str = "cosine", k: int = 14, chunk: int = 8192,
    rules=None, exclude_self: bool = False,
):
    """kNN graph over the landmark representation without the (U, U) matrix:
    scan candidate chunks carrying a running (U, k) top-k. Row-sharded ``rep``
    stays sharded; per-chunk candidate rows (chunk, n) are gathered (tiny).
    The carry is explicitly row-sharded — an unconstrained scan carry would be
    resolved replicated and drag the whole (U, chunk) sims buffer with it.

    U that is not a multiple of ``chunk`` is handled by padding the candidate
    side (padded columns are masked to -inf, so no row is ever counted twice);
    ``exclude_self`` masks the diagonal so row u never lists itself."""
    from repro.distributed.sharding import constrain

    u, n = rep.shape
    chunk = max(min(chunk, u), min(k, u))
    n_chunks = -(-u // chunk)
    pad = n_chunks * chunk - u
    cand_src = jnp.pad(rep, ((0, pad), (0, 0))) if pad else rep
    row_ids = jnp.arange(u)
    pin = lambda x: constrain(x, ("batch", "null"), rules) if rules else x

    def body(carry, c_idx):
        best_v, best_i = carry
        cand = jax.lax.dynamic_slice_in_dim(cand_src, c_idx * chunk, chunk, axis=0)
        sims = pin(dense_similarity(rep, cand, measure))  # (U, chunk) row-sharded
        cand_ids = c_idx * chunk + jnp.arange(chunk)
        invalid = (cand_ids >= u)[None, :]
        if exclude_self:
            invalid = invalid | (cand_ids[None, :] == row_ids[:, None])
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, k)
        i = i + c_idx * chunk
        mv = jnp.concatenate([best_v, v], axis=1)
        mi = jnp.concatenate([best_i, i], axis=1)
        nv, sel = jax.lax.top_k(mv, k)
        return (pin(nv), pin(jnp.take_along_axis(mi, sel, axis=1))), None

    init = (pin(jnp.full((u, k), -jnp.inf, jnp.float32)),
            pin(jnp.zeros((u, k), jnp.int32)))
    (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return vals, idx


def streaming_knn_graph_sharded(
    rep: jax.Array, mesh, measure: str = "cosine", k: int = 14,
    chunk_local: int = 512, row_axes=("pod", "data"),
    exclude_self: bool = False, n_valid: Optional[int] = None,
):
    """shard_map variant: rows stay local per shard, candidate chunks are
    all-gathered one at a time (chunk_local × n_shards rows per step). No
    GSPMD decisions — top_k is shard-local by construction.

    Global candidate ids: a tiled all_gather over ``axes`` concatenates the
    per-shard chunks in mesh-linearized shard order, so gathered column j is
    local row ``c_idx * chunk_local + j % chunk_local`` of shard
    ``j // chunk_local`` — whose global row id is ``shard * u_local + local``
    (rows are block-partitioned over the same linearization). Verified against
    the unsharded oracle in tests/test_distributed.py, including multi-axis
    meshes.

    ``n_valid`` (static) marks trailing global rows as padding (ragged U
    rounded up to the shard count): they are never selected as candidates,
    and their own query rows are garbage the caller slices off."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(a for a in row_axes if a in mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if n_valid is None:
        n_valid = rep.shape[0]

    def inner(rep_l):
        u_l, n = rep_l.shape
        # Candidate-side chunking adapts to the local shard: clamp to u_l,
        # grow so one gathered step holds >= k candidates (top_k needs that),
        # and pad the candidate source so ragged u_l never double-counts rows
        # (padded local indices are masked invalid below). Queries stay the
        # unpadded rep_l, so outputs keep the (u_l, k) shard shape.
        chunk = max(min(chunk_local, u_l), -(-k // n_shards))
        n_chunks = -(-u_l // chunk)
        pad = n_chunks * chunk - u_l
        cand_src = jnp.pad(rep_l, ((0, pad), (0, 0))) if pad else rep_l
        shard_lin = jnp.int32(0)
        for a in axes:
            shard_lin = shard_lin * mesh.shape[a] + jax.lax.axis_index(a)
        row_gid = shard_lin * u_l + jnp.arange(u_l)
        j = jnp.arange(chunk * n_shards)

        def body(carry, c_idx):
            best_v, best_i = carry
            mine = jax.lax.dynamic_slice_in_dim(cand_src, c_idx * chunk,
                                                chunk, axis=0)
            cand = jax.lax.all_gather(mine, axes, tiled=True)  # (chunk*S, n)
            within = c_idx * chunk + j % chunk  # local row in the padded space
            cand_gid = (j // chunk) * u_l + within
            valid = (within < u_l) & (cand_gid < n_valid)
            sims = dense_similarity(rep_l, cand, measure)
            invalid = ~valid[None, :]
            if exclude_self:
                invalid = invalid | (cand_gid[None, :] == row_gid[:, None])
            sims = jnp.where(invalid, -jnp.inf, sims)
            v, i = jax.lax.top_k(sims, k)
            gid = jnp.where(valid, cand_gid, 0)[i]
            mv = jnp.concatenate([best_v, v], axis=1)
            mi = jnp.concatenate([best_i, gid.astype(jnp.int32)], axis=1)
            nv, sel = jax.lax.top_k(mv, k)
            return (nv, jnp.take_along_axis(mi, sel, axis=1)), None

        init = (jnp.full((u_l, k), -jnp.inf, jnp.float32),
                jnp.zeros((u_l, k), jnp.int32))
        (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
        return vals, idx

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=(P(axes, None), P(axes, None)),
        check_vma=False,
    )(rep)
