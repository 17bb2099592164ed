"""Neighbor-graph construction — the d2/kNN step without the (U, U) matrix.

The fitted artifact of landmark CF is a :class:`~repro.core.types.NeighborGraph`
— per-user top-k neighbor ids + similarity weights, O(U·k) memory. This module
is the single place that turns a (U, n) landmark representation into that
graph, with three selectable backends:

==========  =====================  ============================================
backend     peak memory            when to pick it
==========  =====================  ============================================
dense       O(U²)                  small U / paper-table parity: materializes
                                   the full d2 matrix then top-k's it. Exact
                                   tie-breaking match with the dense oracle.
streaming   O(U·chunk)             default everywhere: scans candidate chunks
                                   carrying a running (U, k) best-list; works
                                   for every d2 measure and sharded reps.
pallas      O(U·k) HBM             TPU hot path, every d2 measure: the fused
                                   sims+top-k kernel with in-kernel
                                   pearson/euclidean epilogues — sims tiles
                                   never leave VMEM (kernels/knn_topk.py).
ivf         O(U·(n+1)·slack)       sublinear candidate generation: a k-means
                                   IVF index over the landmark embedding
                                   (repro.retrieval) prunes each row's scan
                                   to the nprobe nearest cells. Exact
                                   (bit-identical to streaming) at
                                   nprobe == n_clusters; approximate at the
                                   default nprobe (docs/retrieval.md).
==========  =====================  ============================================

``auto`` resolves to ``pallas`` on TPU (any d2 measure), else ``streaming``
(``ivf`` is opt-in: recall@k < 1 at the default nprobe is a policy decision,
never an accident). All backends exclude self and store weight 0 for
empty/invalid slots, so downstream Eq. (1) prediction (core.knn) is
backend-agnostic.

The serve path extends a fitted graph without refitting:
:func:`extend_neighbor_graph` appends b new rows (new-vs-all candidate scan,
never more than a (b, chunk) sims tile) and back-patches the existing rows
whose top-k should now include a new row (one (U, b) block — b ≪ U). Peak
memory is O((U+b)·k + U·b + b·chunk); no (U, U) or (U+b, U+b) intermediate
exists (asserted on the jaxpr in tests/test_graph.py).

:func:`extend_neighbor_graph_bucketed` is the shape-stable variant behind
``repro.lifecycle.buckets``: arrays stay padded to a bucket capacity C and the
valid-row counts are *traced* scalars, so the whole fold-in step compiles once
per (C, batch-bucket) pair instead of once per fold-in. Padded rows are masked
out of both halves of the update — they can never be selected as neighbors.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .similarity import EPS, dense_similarity, streaming_knn_graph
from .types import NeighborGraph

BACKENDS = ("dense", "streaming", "pallas", "ivf", "auto")


def resolve_backend(backend: str, measure: str) -> str:
    """``auto`` → ``pallas`` on TPU for every d2 measure (the kernel applies
    pearson/euclidean epilogues in-kernel since the mesh-serving PR; it used
    to silently fall back to ``streaming`` for non-cosine), else
    ``streaming``."""
    if backend == "auto":
        if jax.default_backend() == "tpu":
            return "pallas"
        return "streaming"
    if backend not in BACKENDS:
        raise ValueError(f"unknown graph backend {backend!r}; expected {BACKENDS}")
    return backend


def _l2_normalize(x: jax.Array) -> jax.Array:
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return (x / jnp.maximum(norm, EPS)).astype(jnp.float32)


def finalize_topk(vals: jax.Array, idx: jax.Array) -> NeighborGraph:
    """Streaming top-k output -> graph: empty (-inf) slots become weight 0."""
    ok = jnp.isfinite(vals)
    return NeighborGraph(
        jnp.where(ok, idx, 0).astype(jnp.int32),
        jnp.where(ok, vals, 0.0).astype(jnp.float32),
    )


def canonical_topk(vals: jax.Array, ids: jax.Array, k: int,
                   rank: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Lexicographic (value desc, rank asc) top-k over candidate columns.

    Every graph list in this repo is stored in that canonical order: the
    streaming/dense/bucketed builds lay candidates out in ascending-id order,
    so ``lax.top_k``'s positional tie-break IS the id-ascending tie-break.
    When merged candidates are *not* in ascending-id order (a mutated row's
    id can be smaller than the incumbent list's ids — ``repro.mutation``;
    a cross-shard candidate gather — ``extend_neighbor_graph_sharded``),
    positional top-k would break exact-weight ties wrongly. Two stable
    argsorts (rank first, then value) emulate the lexicographic top-k
    instead. ``rank`` defaults to ``ids``; sharded callers pass logical row
    ranks so ties canonicalize across the id bijection.
    """
    if rank is None:
        rank = ids
    m = vals.shape[1]
    if m < k:
        pad = k - m
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
        rank = jnp.pad(rank, ((0, 0), (0, pad)),
                       constant_values=jnp.iinfo(jnp.int32).max)
    ord1 = jnp.argsort(rank, axis=1)
    v1 = jnp.take_along_axis(vals, ord1, axis=1)
    i1 = jnp.take_along_axis(ids, ord1, axis=1)
    sel = jnp.argsort(-v1, axis=1)[:, :k]
    return (jnp.take_along_axis(v1, sel, axis=1),
            jnp.take_along_axis(i1, sel, axis=1))


def merge_canonical_topk(av: jax.Array, ai: jax.Array,
                         bv: jax.Array, bi: jax.Array, k: int,
                         a_rank: Optional[jax.Array] = None,
                         b_rank: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Exact lexicographic top-k of two *already canonical* candidate lists.

    ``(av, ai)`` is (rows, ka) and ``(bv, bi)`` is (rows, kb), each in
    canonical (value desc, rank asc) order. The merged position of every
    element is its own index plus the number of elements of the *other*
    list that strictly precede it — the textbook merge-by-rank-count, one
    (rows, ka, kb) boolean compare each way plus a scatter, no sort. On the
    skinny merges ``repro.mutation`` runs per write batch this is an order
    of magnitude cheaper than :func:`canonical_topk`'s two full-width
    stable argsorts (XLA's variadic sort is the write path's bottleneck on
    CPU hosts).

    Exactness requires a strict order across the two lists for entries that
    can reach the top-k: no exact cross-list ``(value, rank)`` tie (call
    sites guarantee it — a patched row's incumbent list is id-disjoint from
    the update batch, and ``-inf``-masked entries never outrank a stored
    finite weight). Cross-list ties among entries that *cannot* reach the
    top-k (two ``-inf`` pads) are harmless: within-list order is preserved
    by construction, and :func:`finalize_topk` collapses any selected pad
    to the inert (0, 0.0) slot regardless of which one won.
    """
    if a_rank is None:
        a_rank = ai
    if b_rank is None:
        b_rank = bi
    rows, ka = av.shape
    kb = bv.shape[1]
    if ka + kb < k:  # degenerate: not enough candidates to fill k slots
        return canonical_topk(jnp.concatenate([av, bv], axis=1),
                              jnp.concatenate([ai, bi], axis=1), k,
                              rank=jnp.concatenate([a_rank, b_rank], axis=1))
    # x ≻ y  ⇔  value greater, or equal value with smaller rank
    b_before_a = (bv[:, :, None] > av[:, None, :]) | (
        (bv[:, :, None] == av[:, None, :])
        & (b_rank[:, :, None] < a_rank[:, None, :]))  # (rows, kb, ka)
    a_before_b = (av[:, :, None] > bv[:, None, :]) | (
        (av[:, :, None] == bv[:, None, :])
        & (a_rank[:, :, None] < b_rank[:, None, :]))  # (rows, ka, kb)
    pos_a = jnp.arange(ka) + jnp.sum(b_before_a, axis=1)
    pos_b = jnp.arange(kb) + jnp.sum(a_before_b, axis=1)
    # invert the position permutation with a gather, not a scatter (XLA's
    # CPU scatter is a serial loop): slot s takes the unique element whose
    # merged position is s — positions are a bijection onto 0..ka+kb-1, so
    # every slot < k matches exactly once
    pos = jnp.concatenate([pos_a, pos_b], axis=1)
    mv = jnp.concatenate([av, bv], axis=1)
    mi = jnp.concatenate([ai, bi], axis=1)
    slot = jnp.argmax(pos[:, None, :] == jnp.arange(k)[None, :, None], axis=2)
    return (jnp.take_along_axis(mv, slot, axis=1),
            jnp.take_along_axis(mi, slot, axis=1))


def evict_neighbors(graph: NeighborGraph, dead: jax.Array,
                    row_rank: Optional[jax.Array] = None
                    ) -> Tuple[NeighborGraph, jax.Array]:
    """Remove every citation of a ``dead`` row id from all neighbor lists.

    ``dead`` is a (capacity,) bool over the graph's id space (tombstoned or
    mutated rows). Dead entries are masked to -inf, lists are re-sorted
    canonically ((value desc, rank asc) — surviving order is unchanged
    because lists are already canonical), and emptied slots become the inert
    (0, 0.0) convention via :func:`finalize_topk`. Returns ``(graph, hit)``
    where ``hit`` is a (capacity,) bool marking rows that lost at least one
    entry — those rows' k-th neighbor is now unknown (the old (k+1)-th
    candidate is not stored) and the caller must schedule a repair rescan
    (``repro.mutation``'s dirty bitmap).

    Only O(capacity·k) gathers run — never a row-space product.
    """
    cited_dead = dead[graph.indices]
    # NOTE: the inert (0, 0.0) convention slot cites id 0, so a dead row 0
    # flags every row holding an inert slot — a spurious-but-safe hit (the
    # rescan reproduces the inert slot). A weight==0 filter would instead
    # let a *genuine* zero-similarity citation of a dead id survive, which
    # breaks the tombstone-absence guarantee; zero-rep users make exact-0.0
    # weights common, so no filter.
    hit = jnp.any(cited_dead, axis=1)
    w = jnp.where(cited_dead, -jnp.inf, graph.weights)
    rank = graph.indices if row_rank is None else row_rank[graph.indices]
    v, i = canonical_topk(w, graph.indices, graph.k, rank=rank)
    g = finalize_topk(v, i)
    return NeighborGraph(jnp.where(hit[:, None], g.indices, graph.indices),
                         jnp.where(hit[:, None], g.weights, graph.weights)), hit


def filter_self_from_topk(vals: jax.Array, idx: jax.Array, row_ids: jax.Array,
                          k: int) -> Tuple[jax.Array, jax.Array]:
    """Drop each row's own id from an inclusive (U, k+1) top-k list.

    For sharded kernel outputs where in-kernel self-exclusion would need the
    shard's global row offset: mask slots whose id equals the row id, then
    re-top-k down to ``k``.
    """
    vals = jnp.where(idx == row_ids[:, None], -jnp.inf, vals)
    v, sel = jax.lax.top_k(vals, k)
    return v, jnp.take_along_axis(idx, sel, axis=1)


def build_neighbor_graph(
    rep: jax.Array,  # (U, n) landmark-space representation
    measure: str = "cosine",
    k: int = 13,
    backend: str = "auto",
    *,
    chunk: int = 4096,
    block: Tuple[int, int] = (128, 512),
    interpret: Optional[bool] = None,
    ivf=None,  # retrieval.IVFSpec for backend="ivf" (None -> defaults)
) -> NeighborGraph:
    """Top-k neighbor graph over ``rep`` rows under d2 ``measure``.

    Self is always excluded. ``k`` is clamped to U-1 (a row cannot have more
    distinct neighbors than other rows). See the module docstring for the
    backend matrix. ``backend="ivf"`` builds a fresh IVF index over ``rep``
    and searches it at ``ivf.nprobe`` (exact when nprobe == n_clusters);
    callers that want to keep the index for the serve path should build it
    themselves via ``repro.retrieval`` and search directly.
    """
    u = rep.shape[0]
    k = max(1, min(k, u - 1)) if u > 1 else 1
    backend = resolve_backend(backend, measure)

    if backend == "dense":
        return NeighborGraph.from_dense_sims(
            dense_similarity(rep, rep, measure), k, exclude_self=True)

    if backend == "streaming":
        vals, idx = streaming_knn_graph(rep, measure, k=k, chunk=chunk,
                                        exclude_self=True)
        return finalize_topk(vals, idx)

    if backend == "ivf":
        from repro.retrieval import build_index, resolve_ivf, search

        cfg = resolve_ivf(ivf, u)
        index = build_index(rep, cfg, measure)
        vals, idx = search(index, rep, k, cfg.nprobe, measure,
                           self_ids=jnp.arange(u))
        return finalize_topk(vals, idx)

    # pallas: fused MXU sims + VMEM-resident top-k. Cosine pre-normalizes
    # rows once outside the kernel; pearson/euclidean run their epilogues
    # in-kernel on the raw representation (kernels/knn_topk.py).
    from repro.kernels.knn_topk import topk_sim_kernel

    repq = _l2_normalize(rep) if measure == "cosine" else rep.astype(jnp.float32)
    vals, idx = topk_sim_kernel(repq, repq, k=k, block=block,
                                interpret=interpret, exclude_self=True,
                                n_valid=u, measure=measure)
    return finalize_topk(vals, idx)


def _streaming_query_topk(
    queries: jax.Array,  # (b, n) new rows
    cand_src: jax.Array,  # (C, n) candidate rows (existing + new)
    measure: str,
    k: int,
    chunk: int,
    self_offset: int,  # query row i is candidate row self_offset + i
) -> Tuple[jax.Array, jax.Array]:
    """Top-k candidates per query row, scanning (b, chunk) sims tiles only."""
    b = queries.shape[0]
    c = cand_src.shape[0]
    chunk = max(min(chunk, c), min(k, c))
    n_chunks = -(-c // chunk)
    pad = n_chunks * chunk - c
    if pad:
        cand_src = jnp.pad(cand_src, ((0, pad), (0, 0)))
    row_gid = self_offset + jnp.arange(b)

    def body(carry, c_idx):
        best_v, best_i = carry
        cand = jax.lax.dynamic_slice_in_dim(cand_src, c_idx * chunk, chunk, axis=0)
        sims = dense_similarity(queries, cand, measure)  # (b, chunk)
        cand_ids = c_idx * chunk + jnp.arange(chunk)
        invalid = (cand_ids >= c)[None, :] | (cand_ids[None, :] == row_gid[:, None])
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, k)
        mv = jnp.concatenate([best_v, v], axis=1)
        mi = jnp.concatenate([best_i, (i + c_idx * chunk).astype(jnp.int32)], axis=1)
        nv, sel = jax.lax.top_k(mv, k)
        return (nv, jnp.take_along_axis(mi, sel, axis=1)), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32), jnp.zeros((b, k), jnp.int32))
    (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return vals, idx


def extend_neighbor_graph(
    graph: NeighborGraph,  # (U, k) fitted graph over ``rep`` rows
    rep: jax.Array,  # (U, n) existing landmark-space rows
    new_rep: jax.Array,  # (b, n) fold-in rows, appended as ids U..U+b-1
    measure: str = "cosine",
    backend: str = "auto",
    *,
    chunk: int = 4096,
    interpret: Optional[bool] = None,
    ivf=None,  # retrieval.IVFSpec for backend="ivf" (None -> defaults)
    ivf_index=None,  # prebuilt retrieval.IVFIndex over the U existing rows
) -> NeighborGraph:
    """Append b rows to a fitted graph without refitting — the serve hot path.

    Two halves, mirroring Lu & Shen's new-user similarity-list update:

    1. **new-vs-all**: each new row scans all U+b candidates for its own top-k
       (streaming (b, chunk) tiles; the ``pallas`` backend runs the skinny
       fold-in kernel with the whole query block VMEM-resident; the ``ivf``
       backend appends the batch to an IVF index over the existing rows and
       probes only the nprobe nearest cells — O(b·(U/C)·nprobe·n) candidate
       generation instead of O(b·U·n), Lu & Shen's new-user case made
       sublinear. Pass the serve loop's live ``ivf_index`` to skip the
       O(U) on-the-fly build; exact at nprobe == n_clusters.)
    2. **back-patch**: the (U, b) existing-vs-new block is merged into the
       existing rows' best-lists, so an old user whose true top-k now contains
       a new user is updated too — extend followed by extend matches one
       bigger extend.

    Exactness vs a from-scratch build on the concatenated rows holds when the
    fitted graph was built with k ≤ U-1 (no empty slots: an empty slot stores
    weight 0, which would shadow a negative-similarity candidate) and modulo
    top-k tie-breaking. ``k`` stays ``graph.k``: fold-in never widens lists.
    Compact (uint16/bf16) graphs are widened first; the result is full
    precision (re-compact via ``NeighborGraph.to_compact``).
    """
    if graph.is_compact:
        graph = graph.to_full()
    u = rep.shape[0]
    b = new_rep.shape[0]
    k = graph.k
    backend = resolve_backend(backend, measure)

    # -- 1. new-vs-all: top-k rows for the b appended users -------------------
    if backend == "pallas":
        from repro.kernels.knn_topk import foldin_topk_kernel

        norm = _l2_normalize if measure == "cosine" else \
            (lambda x: x.astype(jnp.float32))
        cand = jnp.concatenate([norm(rep), norm(new_rep)])
        vals, idx = foldin_topk_kernel(norm(new_rep), cand, k=k,
                                       block_c=min(chunk, 512),
                                       interpret=interpret, self_offset=u,
                                       measure=measure)
    elif backend == "ivf":
        import dataclasses as _dc

        from repro.retrieval import (IVFSpec, build_index, grow_capacity,
                                     resolve_ivf, search)
        from repro.retrieval import append as ivf_append

        if ivf_index is None:
            cfg = resolve_ivf(ivf, u)
            ivf_index = build_index(rep, cfg, measure)
        else:
            cfg = resolve_ivf(_dc.replace(ivf or IVFSpec(),
                                          n_clusters=ivf_index.n_clusters), u)
        # the index covers the u existing rows; if the batch could exceed the
        # total free slots, reserve room NOW (static shapes, so this works
        # under the jitted fold_in — append cannot raise on overflow, it
        # would silently drop rows and break exactness)
        c_lists, cap = ivf_index.n_clusters, ivf_index.capacity
        if u + b > c_lists * cap:
            from repro.core.types import round_up as _round_up

            ivf_index = grow_capacity(
                ivf_index,
                _round_up(max(-(-int((u + b) * cfg.slack) // c_lists),
                              -(-(u + b) // c_lists)), 8))
        # the batch rows are candidates for each other too: append first,
        # search after — every candidate sits in exactly one posting list
        with_batch = ivf_append(ivf_index, new_rep,
                                u + jnp.arange(b, dtype=jnp.int32), measure,
                                spill_choices=cfg.spill_choices)
        vals, idx = search(with_batch, new_rep, k, cfg.nprobe, measure,
                           self_ids=u + jnp.arange(b, dtype=jnp.int32))
    elif backend == "dense":
        # small-U parity path: one (b, U+b) block, still skinny (b ≪ U).
        cand = jnp.concatenate([rep, new_rep])
        sims = dense_similarity(new_rep, cand, measure)
        gid = jnp.arange(u + b)
        sims = jnp.where(gid[None, :] == (u + jnp.arange(b))[:, None],
                         -jnp.inf, sims)
        vals, idx = jax.lax.top_k(sims, k)
    else:
        cand = jnp.concatenate([rep, new_rep])
        vals, idx = _streaming_query_topk(new_rep, cand, measure, k, chunk,
                                          self_offset=u)
    new_rows = finalize_topk(vals, idx)

    # -- 2. back-patch: merge the (U, b) existing-vs-new block ----------------
    back = dense_similarity(rep, new_rep, measure)  # (U, b)
    new_ids = jnp.broadcast_to(u + jnp.arange(b, dtype=jnp.int32), (u, b))
    mv = jnp.concatenate([graph.weights, back], axis=1)  # (U, k+b)
    mi = jnp.concatenate([graph.indices, new_ids], axis=1)
    pv, sel = jax.lax.top_k(mv, k)
    pi = jnp.take_along_axis(mi, sel, axis=1)

    return NeighborGraph(
        jnp.concatenate([pi, new_rows.indices]),
        jnp.concatenate([pv, new_rows.weights]),
    )


def extend_neighbor_graph_sharded(
    graph: NeighborGraph,  # (S*C, k) block-partitioned capacity-padded graph
    rep: jax.Array,  # (S*C, n) row-sharded rep, new batch ALREADY written
    new_rep: jax.Array,  # (bq, n) replicated batch; rows >= b_valid are filler
    n_valid: jax.Array,  # (S,) int32 per-shard fill BEFORE this extend
    b_valid: jax.Array,  # () int32 real rows in the batch
    target_shard: jax.Array,  # () int32 shard that receives the batch
    mesh,
    measure: str = "cosine",
    *,
    row_axes=("pod", "data"),
    row_rank: Optional[jax.Array] = None,  # (S*C,) logical id per slot
) -> NeighborGraph:
    """:func:`extend_neighbor_graph_bucketed` on a mesh — the sharded serve
    fold-in (ROADMAP: "fold-in for the sharded graph").

    Row ids are block-partitioned: shard s (mesh-linearized over ``row_axes``,
    same linearization as ``streaming_knn_graph_sharded``) owns ids
    ``[s*C, (s+1)*C)``; the batch lands in shard ``target_shard``'s padded
    slots (its rep rows are already written there — shard-local append). Three
    shard-local phases, one cross-shard collective:

    1. **new-vs-all** — every shard scores the replicated (bq, n) queries
       against its own (C, n) block and takes a local top-k; one
       all-gather of the (bq, k) candidate lists (ids travel with values)
       followed by a replicated merge gives each new row its global top-k.
       The only collective payload is O(bq·k·S) — never a row of ``rep``.
       The merge breaks exact-weight ties by ``row_rank`` (logical arrival
       order) — the same total order the single-device scan's slot order
       implies — so duplicate d1 representations cannot make the sharded
       neighbor lists diverge from the single-device ones.
    2. **back-patch** — each shard merges its local (C, bq) existing-vs-new
       block into rows below its own fill mark, entirely shard-local.
    3. **append** — the target shard writes the merged new rows at its fill
       offset; filler batch rows store (0, 0.0), preserving the padded-graph
       invariant.

    Every mask is traced (per-shard fills, batch fill, target), so one
    executable serves all fold-ins at a given (C, bq) — the bucket discipline
    survives the mesh. Oracle-exact vs the single-device bucketed fold-in
    modulo the dense↔sharded id bijection (tests/test_sharded_serving.py).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import cf_row_axes, cf_shard_count, \
        shard_linear_index

    if graph.is_compact:
        graph = graph.to_full()
    axes = cf_row_axes(mesh, row_axes)
    n_shards = cf_shard_count(mesh, axes)
    c = rep.shape[0] // n_shards  # per-shard capacity
    bq = new_rep.shape[0]
    k = graph.k
    kk = min(k, c)
    if row_rank is None:  # fall back to sharded-id order (block == logical)
        row_rank = jnp.arange(rep.shape[0], dtype=jnp.int32)

    def inner(gi_l, gw_l, rep_l, rank_l, new_rep, n_valid, b_valid, target):
        lin = shard_linear_index(mesh, axes)
        mine = lin == target
        my_valid = n_valid[lin]
        base_gid = lin * c
        new_gid = target * c + n_valid[target] + jnp.arange(bq, dtype=jnp.int32)
        slot = jnp.arange(c)

        # -- 1. new-vs-all: local candidates, local top-k, gathered merge ----
        sims = dense_similarity(new_rep, rep_l, measure)  # (bq, C)
        limit = my_valid + jnp.where(mine, b_valid, 0)  # batch rows count here
        invalid = ((slot >= limit)[None, :]
                   | ((base_gid + slot)[None, :] == new_gid[:, None]))
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, kk)  # ties -> lowest slot == lowest rank
        g = base_gid + i
        r = rank_l[i]
        vs = jax.lax.all_gather(v, axes, axis=1, tiled=True)  # (bq, kk*S)
        gs = jax.lax.all_gather(g, axes, axis=1, tiled=True)
        rs = jax.lax.all_gather(r, axes, axis=1, tiled=True)
        # canonical merge: weight desc, logical rank asc — two stable
        # argsorts (rank first, then value) emulate the lexicographic top-k
        ord1 = jnp.argsort(rs, axis=1)
        vs1 = jnp.take_along_axis(vs, ord1, axis=1)
        gs1 = jnp.take_along_axis(gs, ord1, axis=1)
        sel = jnp.argsort(-vs1, axis=1)[:, :k]
        nv = jnp.take_along_axis(vs1, sel, axis=1)
        ni = jnp.take_along_axis(gs1, sel, axis=1)
        ok = jnp.isfinite(nv) & (jnp.arange(bq) < b_valid)[:, None]
        new_idx = jnp.where(ok, ni, 0).astype(jnp.int32)
        new_w = jnp.where(ok, nv, 0.0).astype(jnp.float32)

        # -- 2. back-patch local valid rows with the valid batch columns -----
        back = dense_similarity(rep_l, new_rep, measure)  # (C, bq)
        back = jnp.where((jnp.arange(bq) < b_valid)[None, :], back, -jnp.inf)
        mv = jnp.concatenate([gw_l, back], axis=1)  # (C, k + bq)
        mi = jnp.concatenate(
            [gi_l, jnp.broadcast_to(new_gid[None, :], (c, bq))], axis=1)
        pv, psel = jax.lax.top_k(mv, k)
        pi = jnp.take_along_axis(mi, psel, axis=1)
        r_valid = (slot < my_valid)[:, None]
        gi2 = jnp.where(r_valid, pi, gi_l)
        gw2 = jnp.where(r_valid, pv, gw_l)

        # -- 3. append the new rows on the target shard ----------------------
        gi3 = jax.lax.dynamic_update_slice(gi2, new_idx, (n_valid[target], 0))
        gw3 = jax.lax.dynamic_update_slice(gw2, new_w, (n_valid[target], 0))
        return jnp.where(mine, gi3, gi2), jnp.where(mine, gw3, gw2)

    row = P(axes, None)
    gi, gw = shard_map(
        inner, mesh=mesh,
        in_specs=(row, row, row, P(axes), P(None, None), P(None), P(), P()),
        out_specs=(row, row), check_vma=False,
    )(graph.indices, graph.weights, rep, row_rank, new_rep, n_valid, b_valid,
      target_shard)
    return NeighborGraph(gi, gw)


def _bucketed_query_topk(
    queries: jax.Array,  # (bq, n) batch-bucket rows (padded)
    cand_src: jax.Array,  # (C, n) capacity-padded candidate rows
    measure: str,
    k: int,
    chunk: int,
    n_valid: jax.Array,  # () rows < n_valid were valid before this extend
    b_valid: jax.Array,  # () first b_valid queries are real
) -> Tuple[jax.Array, jax.Array]:
    """Masked top-k over a capacity-padded candidate block, (bq, chunk) tiles.

    Valid candidates are exactly rows ``< n_valid + b_valid`` (the new batch is
    written contiguously at ``n_valid`` before this runs); query i excludes its
    own slot ``n_valid + i``. All masks are traced, so the executable is shared
    by every fold-in at this (C, bq) shape.
    """
    bq = queries.shape[0]
    c = cand_src.shape[0]
    chunk = max(min(chunk, c), min(k, c))
    n_chunks = -(-c // chunk)
    pad = n_chunks * chunk - c
    if pad:
        cand_src = jnp.pad(cand_src, ((0, pad), (0, 0)))
    row_gid = n_valid + jnp.arange(bq)

    def body(carry, c_idx):
        best_v, best_i = carry
        cand = jax.lax.dynamic_slice_in_dim(cand_src, c_idx * chunk, chunk, axis=0)
        sims = dense_similarity(queries, cand, measure)  # (bq, chunk)
        cand_ids = c_idx * chunk + jnp.arange(chunk)
        invalid = ((cand_ids >= n_valid + b_valid)[None, :]
                   | (cand_ids[None, :] == row_gid[:, None]))
        sims = jnp.where(invalid, -jnp.inf, sims)
        v, i = jax.lax.top_k(sims, k)
        mv = jnp.concatenate([best_v, v], axis=1)
        mi = jnp.concatenate([best_i, (i + c_idx * chunk).astype(jnp.int32)], axis=1)
        nv, sel = jax.lax.top_k(mv, k)
        return (nv, jnp.take_along_axis(mi, sel, axis=1)), None

    init = (jnp.full((bq, k), -jnp.inf, jnp.float32), jnp.zeros((bq, k), jnp.int32))
    (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return vals, idx


def extend_neighbor_graph_bucketed(
    graph: NeighborGraph,  # (C, k) capacity-padded graph
    rep: jax.Array,  # (C, n) rep with the new batch ALREADY written at n_valid
    new_rep: jax.Array,  # (bq, n) batch-bucket rows; rows >= b_valid are filler
    n_valid: jax.Array,  # () int32 valid rows BEFORE this extend
    b_valid: jax.Array,  # () int32 real rows in the batch bucket
    measure: str = "cosine",
    *,
    chunk: int = 4096,
) -> NeighborGraph:
    """Shape-stable :func:`extend_neighbor_graph`: same (C, k) graph out.

    The two halves mirror the growing variant, with padding masked throughout:

    1. **new-vs-all** — each batch row scans the valid prefix (ids
       ``< n_valid + b_valid``) for its top-k; its rows land in graph slots
       ``[n_valid, n_valid + bq)``. Filler batch rows are stored as (0, 0.0)
       so the padded-graph invariant (weight 0 everywhere above the valid
       prefix) is preserved.
    2. **back-patch** — the (C, bq) existing-vs-new block is merged into rows
       ``< n_valid`` only; filler batch columns are -inf so they can never
       displace a real neighbor.

    Because every mask is a traced scalar, one executable serves all fold-ins
    at a given (C, bq); recompiles happen only on bucket growth.
    """
    if graph.is_compact:
        graph = graph.to_full()
    bq = new_rep.shape[0]
    c = rep.shape[0]
    k = graph.k

    # -- 1. new-vs-all over the valid prefix ---------------------------------
    vals, idx = _bucketed_query_topk(new_rep, rep, measure, k, chunk,
                                     n_valid, b_valid)
    new_rows = finalize_topk(vals, idx)
    q_valid = (jnp.arange(bq) < b_valid)[:, None]
    new_idx = jnp.where(q_valid, new_rows.indices, 0)
    new_w = jnp.where(q_valid, new_rows.weights, 0.0)

    # -- 2. back-patch valid existing rows with the valid batch columns ------
    back = dense_similarity(rep, new_rep, measure)  # (C, bq)
    back = jnp.where((jnp.arange(bq) < b_valid)[None, :], back, -jnp.inf)
    batch_ids = (n_valid + jnp.arange(bq, dtype=jnp.int32))[None, :]
    mv = jnp.concatenate([graph.weights, back], axis=1)  # (C, k + bq)
    mi = jnp.concatenate([graph.indices, jnp.broadcast_to(batch_ids, (c, bq))],
                         axis=1)
    pv, sel = jax.lax.top_k(mv, k)
    pi = jnp.take_along_axis(mi, sel, axis=1)
    r_valid = (jnp.arange(c) < n_valid)[:, None]
    indices = jnp.where(r_valid, pi, graph.indices)
    weights = jnp.where(r_valid, pv, graph.weights)

    # write the batch rows into their slots (traced offset, static shapes)
    indices = jax.lax.dynamic_update_slice(indices, new_idx, (n_valid, 0))
    weights = jax.lax.dynamic_update_slice(weights, new_w, (n_valid, 0))
    return NeighborGraph(indices, weights)
