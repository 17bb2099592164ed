"""Sharded IVF — posting lists living alongside ``ShardedLandmarkState``.

PR 4 sharded the serving state but left retrieval a full mesh scan: every
request paid one pass over all U rows (``streaming_knn_graph_sharded``) plus
a per-chunk all-gather. This module gives the mesh the same sublinear probe
path the single-device index has, with the request-path collectives bounded
to one (k,)-sized merge:

  layout    cells are block-partitioned shard-major over the row axes —
            shard ``s`` (the ``shard_linear_index`` linearization, identical
            to the S*C+slot row id space) owns cells [s*C_ps, (s+1)*C_ps),
            C_ps = C/S, with ``lists``/``rows``/``scale`` sharded
            ``P(axes, None, ...)`` and the small ``centroids``/``fill``
            replicated. Posting lists store *logical* row ids, so results
            merge across shards without translation. ``resolve_ivf_sharded``
            rounds C up to a multiple of S.

  append    the placement *plan* (``index.place_plan``) is computed
            replicated — destinations depend only on (fill, choices), both
            replicated — and each shard applies the scatter for the
            destinations it owns. No collective beyond the already-
            replicated batch.

  search    each query's probe list is computed replicated (centroids are
            replicated), then a ``shard_map`` router hands every shard only
            the probed cells it owns: the shard sorts its local probe hits
            first, scores at most ``local_budget`` cells (exactly C_ps at
            full probe — a perfect S-way split), reduces to a local top-k,
            and one ``all_gather`` of the (b, k) lists + a canonical
            (value desc, id asc) merge produces the replicated result. The
            request path moves O(b·k·S) floats — never candidate rows.

At full probe the local scorer is the same id-sorted slice+GEMM as the
single-device exact path, per shard block, and the canonical merge is the
associative form of its tie-break — so ``search_sharded`` at
``nprobe == C`` is **bit-identical** to single-device ``search`` (tested in
tests/test_sharded_retrieval.py, the shadow-replica pattern of
test_sharded_serving). Partial probes score with the same scorers as
``search`` (``fused`` Pallas kernel on TPU via ``probe_ok`` masking, the
gathered multiply-reduce elsewhere) and are judged by recall, exactly like
the single-device approximate path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.similarity import dense_similarity
from repro.core.types import round_up
from repro.distributed.sharding import (cf_row_sharding, cf_shard_count,
                                        shard_linear_index)
from repro.kernels.ivf_probe import INT_MAX, fused_probe_topk

from .index import (IVFIndex, IVFSpec, _gathered_sims, _list_choices,
                    _padded_topk, _scatter_entries, dequantize_payload,
                    ensure_index_capacity, place_plan, quantize_payload,
                    resolve_ivf, resolve_scorer)


def resolve_ivf_sharded(spec: Optional[IVFSpec], u: int,
                        n_shards: int) -> IVFSpec:
    """:func:`resolve_ivf` with C rounded up to a multiple of the shard
    count, so the cell axis block-partitions evenly (every shard owns
    exactly C/S cells — the full-probe router budget)."""
    base = spec or IVFSpec()
    r = resolve_ivf(base, u)
    c = round_up(r.n_clusters, max(n_shards, 1))
    t = c if base.spill_choices <= 0 else min(base.spill_choices, c)
    return dataclasses.replace(r, n_clusters=c, nprobe=min(r.nprobe, c),
                               spill_choices=t)


def shard_index(index: IVFIndex, mesh: Mesh, axes) -> IVFIndex:
    """Place an index's arrays onto the mesh: posting payload row-sharded
    over the cell axis, quantizer + fills replicated."""
    s = cf_shard_count(mesh, axes)
    if index.n_clusters % s:
        raise ValueError(
            f"C={index.n_clusters} not divisible by {s} shards — build with "
            "resolve_ivf_sharded")
    rep1 = NamedSharding(mesh, P(None))
    rep2 = NamedSharding(mesh, P(None, None))
    return IVFIndex(
        jax.device_put(index.centroids, rep2),
        jax.device_put(index.lists, cf_row_sharding(mesh, axes, ndim=2)),
        jax.device_put(index.rows, cf_row_sharding(mesh, axes, ndim=3)),
        jax.device_put(index.fill, rep1),
        None if index.scale is None
        else jax.device_put(index.scale, cf_row_sharding(mesh, axes, ndim=2)))


def build_index_sharded(rep: jax.Array, spec: IVFSpec, mesh: Mesh, axes,
                        measure: str = "cosine",
                        n_valid: Optional[jax.Array] = None,
                        key: Optional[jax.Array] = None) -> IVFIndex:
    """Full (re)build + mesh placement. The k-means fit and packing are the
    single-device ``build_index`` (global quantizer, global plan — bitwise
    the same index regardless of mesh), only the residency is sharded."""
    from .index import build_index

    return shard_index(build_index(rep, spec, measure, n_valid=n_valid,
                                   key=key), mesh, axes)


def ensure_index_capacity_sharded(index: IVFIndex, incoming: int, mesh: Mesh,
                                  axes, slack: float = 1.25
                                  ) -> Tuple[IVFIndex, bool]:
    """Sharded capacity regrow: the pure-device ``jnp.pad`` of
    :func:`index.grow_capacity` pads the *slot* axis, which is unsharded —
    GSPMD keeps every posting block on its shard, so growth is one
    block-local device copy (the elastic-mesh half of the ROADMAP item);
    re-placement just re-asserts the shardings."""
    grown, grew = ensure_index_capacity(index, incoming, slack)
    return (shard_index(grown, mesh, axes) if grew else grown), grew


@functools.partial(jax.jit, static_argnames=("mesh", "axes", "measure",
                                             "spill_choices"))
def append_sharded(
    index: IVFIndex,
    new_rep: jax.Array,  # (b, n) replicated fold-in rows
    new_ids: jax.Array,  # (b,) logical row ids (already sharded-id space)
    mesh: Mesh,
    axes: Tuple[str, ...],
    measure: str = "cosine",
    b_valid: Optional[jax.Array] = None,
    spill_choices: int = 0,
) -> IVFIndex:
    """Masked fold-in append, sharded apply: plan replicated, scatter local.

    Bit-equal to single-device :func:`index.append` on the gathered arrays —
    the plan is literally the same ``place_plan`` call on replicated
    (fill, choices), and each shard applies the disjoint subset of writes
    landing in its cells.
    """
    if index.is_compact:
        index = index.to_full()
    s = cf_shard_count(mesh, axes)
    c, cap = index.n_clusters, index.capacity
    c_ps = c // s
    b = new_rep.shape[0]
    valid = (jnp.arange(b) < b_valid) if b_valid is not None \
        else jnp.ones((b,), bool)
    t = c if spill_choices <= 0 else spill_choices
    choices = _list_choices(new_rep, index.centroids, measure, t)
    payload, pscale = quantize_payload(new_rep.astype(jnp.float32),
                                       index.payload_dtype)
    dest_c, dest_s, ok, new_fill = place_plan(index.fill, choices, valid, cap)

    opt_scale = [index.scale] if index.scale is not None else []
    opt_ps = [pscale] if pscale is not None else []

    def inner(lists_l, rows_l, scale_l, ids, payload, ps, dest_c, dest_s, ok):
        lin = shard_linear_index(mesh, axes)
        local = ok & ((dest_c // c_ps) == lin)
        ll, rr, sc = _scatter_entries(
            lists_l, rows_l, scale_l[0] if scale_l else None,
            ids, payload, ps[0] if ps else None,
            dest_c - lin * c_ps, dest_s, local, c_ps)
        return ll, rr, ([sc] if sc is not None else [])

    row2, row3 = P(axes, None), P(axes, None, None)
    lists, rows, scale = shard_map(
        inner, mesh=mesh,
        in_specs=(row2, row3, [row2] * len(opt_scale), P(None),
                  P(None, None), [P(None)] * len(opt_ps), P(None), P(None),
                  P(None)),
        out_specs=(row2, row3, [row2] * len(opt_scale)),
        check_vma=False,
    )(index.lists, index.rows, opt_scale, new_ids.astype(jnp.int32), payload,
      opt_ps, dest_c, dest_s, ok)
    return IVFIndex(index.centroids, lists, rows, new_fill,
                    scale[0] if scale else None)


def _canon_topk(vals: jax.Array, ids: jax.Array, k: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Canonical (value desc, id asc) top-k of (b, m) columns — the
    order-invariant merge ``extend_neighbor_graph_sharded`` uses, so merging
    shard results in any shard order gives one bitwise answer. Two stable
    argsorts — O(m log m), fine at merge width (S·k); the wide per-shard
    candidate rows go through :func:`_fast_topk` instead."""
    if vals.shape[1] < k:
        pad = k - vals.shape[1]
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=INT_MAX)
    o1 = jnp.argsort(ids, axis=1)
    v1 = jnp.take_along_axis(vals, o1, axis=1)
    i1 = jnp.take_along_axis(ids, o1, axis=1)
    sel = jnp.argsort(-v1, axis=1)[:, :k]
    return (jnp.take_along_axis(v1, sel, axis=1),
            jnp.take_along_axis(i1, sel, axis=1))


def _fast_topk(vals: jax.Array, ids: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Local top-k over the wide (b, budget·cap) candidate row: one
    ``lax.top_k`` with its positional tie-break instead of the canonical
    sort pair — ~20x cheaper on CPU, where the two argsorts over thousands
    of columns dominate the whole probe (they cost more than the streaming
    baseline's full-shard GEMM). Deterministic (gather order is fixed per
    shard), but value ties resolve by slot position, not id — fine on the
    approximate path, whose contract is recall; the exact full-probe branch
    and the cross-shard merge keep :func:`_canon_topk` semantics."""
    if vals.shape[1] < k:
        return _canon_topk(vals, ids, k)
    lv, sel = jax.lax.top_k(vals, k)
    return lv, jnp.take_along_axis(ids, sel, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "mesh", "axes",
                                             "measure", "scorer",
                                             "local_budget"))
def search_sharded(
    index: IVFIndex,
    queries: jax.Array,  # (b, n) replicated query rows
    k: int,
    nprobe: int,
    mesh: Mesh,
    axes: Tuple[str, ...],
    measure: str = "cosine",
    *,
    self_ids: Optional[jax.Array] = None,  # (b,) logical id to exclude
    scorer: str = "auto",
    local_budget: Optional[int] = None,
    tomb: Optional[jax.Array] = None,  # (S*C,) replicated tombstone bitmap
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Probe-routed sharded search: (vals, ids, probed), all replicated.

    ``tomb`` masks deleted rows at score time — posting lists keep logical
    row ids, and the bitmap is replicated, so the mask is shard-local
    (``tomb[candidate_id]``) with no extra collective. Like the
    single-device path, a tombstone operand forces the gathered scorer
    (the fused kernel has no tomb input).

    Each shard scores only probed cells it owns, local-first: probe columns
    are stably sorted so a shard's hits lead, and at most ``local_budget``
    ranks are scored (default: ``nprobe`` — nothing dropped; at full probe
    always exactly C/S, the even split). A serving caller sets
    ``local_budget ≈ 2·ceil(nprobe/S)`` to bound tail latency — dropped
    cells degrade recall exactly like a smaller nprobe, which the SLO
    escalation already measures and corrects. ``probed`` (b,) counts cells
    actually scored across all shards, the wave-stats bandwidth metric.

    Collectives on the request path: one psum of the (b,) probe counts and
    one all-gather of the (b, k) local lists — candidate rows never move.
    """
    if index.is_compact:
        index = index.to_full()
    s = cf_shard_count(mesh, axes)
    c, cap = index.n_clusters, index.capacity
    c_ps = c // s
    n = index.rows.shape[2]
    nprobe = min(nprobe, c)
    full = nprobe >= c
    budget = c_ps if full else min(local_budget or nprobe, nprobe)
    b = queries.shape[0]
    q = queries.astype(jnp.float32)
    sids = (self_ids.astype(jnp.int32) if self_ids is not None
            else jnp.full((b,), -1, jnp.int32))
    csims = dense_similarity(q, index.centroids, measure)
    _, probe = jax.lax.top_k(csims, nprobe)  # (b, nprobe) replicated
    probe = probe.astype(jnp.int32)
    use_fused = (resolve_scorer(scorer) in ("fused", "pallas")
                 and tomb is None)
    slot = jnp.arange(cap)
    opt_scale = [index.scale] if index.scale is not None else []
    opt_tomb = [tomb] if tomb is not None else []

    def inner(q, probe, sids, lists_l, rows_l, scale_l, fill, tomb_r):
        lin = shard_linear_index(mesh, axes)
        scale_l = scale_l[0] if scale_l else None
        tomb_r = tomb_r[0] if tomb_r else None
        local = (probe // c_ps) == lin  # (b, nprobe)
        order = jnp.argsort(~local, axis=1)  # stable: local hits lead,
        pr = jnp.take_along_axis(probe, order, axis=1)[:, :budget]
        ok = jnp.take_along_axis(local, order, axis=1)[:, :budget]
        probed = jnp.sum(ok, axis=1).astype(jnp.int32)

        if full:
            # exact local path: the single-device id-sorted slice+GEMM on
            # this shard's block — positional top_k tie-break == canonical
            fill_l = jax.lax.dynamic_slice(fill, (lin * c_ps,), (c_ps,))
            flat = lists_l.reshape(-1).astype(jnp.int32)
            fvalid = (slot[None, :] < fill_l[:, None]).reshape(-1)
            o = jnp.argsort(jnp.where(fvalid, flat, INT_MAX))
            flat, fvalid = flat[o], fvalid[o]
            cmat = dequantize_payload(
                rows_l.reshape(c_ps * cap, n)[o],
                None if scale_l is None else scale_l.reshape(-1)[o])
            sims = dense_similarity(q, cmat, measure)
            invalid = (~fvalid)[None, :] | (flat[None, :] == sids[:, None])
            if tomb_r is not None:
                invalid = invalid | (fvalid & tomb_r[flat])[None, :]
            lv, li = _padded_topk(jnp.where(invalid, -jnp.inf, sims),
                                  jnp.broadcast_to(flat, sims.shape), k)
        elif use_fused:
            lv, li = fused_probe_topk(
                q, jnp.where(ok, pr - lin * c_ps, 0), lists_l, rows_l,
                scale_l, jax.lax.dynamic_slice(fill, (lin * c_ps,), (c_ps,)),
                k=k, measure=measure, self_ids=sids, probe_ok=ok)
            li = jnp.where(jnp.isneginf(lv), INT_MAX, li)
        else:
            # one budget-bounded gather: the shard's working set is
            # (b, budget*cap, n) — an S-times smaller slice than the
            # (b, nprobe*cap, n) HBM candidate tensor a single device
            # materializes, which is the router's whole point
            lc = jnp.where(ok, pr - lin * c_ps, 0)  # (b, budget)
            m = budget * cap
            cand = dequantize_payload(
                rows_l[lc].reshape(b, m, n),
                None if scale_l is None else scale_l[lc].reshape(b, m))
            cc = lists_l[lc].reshape(b, m).astype(jnp.int32)
            live = (ok[:, :, None]
                    & (slot[None, None, :]
                       < fill[jnp.clip(pr, 0, c - 1)][:, :, None]))
            sims = _gathered_sims(q, cand, measure)
            bad = ~live.reshape(b, m) | (cc == sids[:, None])
            if tomb_r is not None:
                bad = bad | tomb_r[cc]
            sims = jnp.where(bad, -jnp.inf, sims)
            lv, li = _fast_topk(sims, cc, k)
            li = jnp.where(jnp.isneginf(lv), INT_MAX, li)

        # the only request-path collectives: (b,) counts + (b, k) lists
        probed = jax.lax.psum(probed, axes)
        av = jax.lax.all_gather(lv, axes)  # (S, b, k)
        ai = jax.lax.all_gather(li, axes)
        mv, mi = _canon_topk(
            jnp.moveaxis(av, 0, 1).reshape(b, -1),
            jnp.moveaxis(ai, 0, 1).reshape(b, -1), k)
        return mv, jnp.where(jnp.isneginf(mv), 0, mi), probed

    row2, row3 = P(axes, None), P(axes, None, None)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(None), row2, row3,
                  [row2] * len(opt_scale), P(None),
                  [P(None)] * len(opt_tomb)),
        out_specs=(P(None, None), P(None, None), P(None)),
        check_vma=False,
    )(q, probe, sids, index.lists, index.rows, opt_scale, index.fill,
      opt_tomb)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "mesh", "axes",
                                             "measure", "patience",
                                             "local_budget"))
def search_early_exit_sharded(
    index: IVFIndex,
    queries: jax.Array,  # (b, n) replicated query rows
    k: int,
    nprobe: int,
    mesh: Mesh,
    axes: Tuple[str, ...],
    measure: str = "cosine",
    *,
    self_ids: Optional[jax.Array] = None,
    patience: int = 2,
    local_budget: Optional[int] = None,
    tomb: Optional[jax.Array] = None,  # (S*C,) replicated tombstone bitmap
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-query early exit with the ``search_sharded`` routing treatment.

    Same probe router: the replicated probe list is sorted local-first per
    shard and clipped to ``local_budget`` ranks, so each shard scans only
    cells it owns, in its local probe-preference order. On top of that each
    shard runs the single-device adaptive traversal (``search_early_exit``):
    a query stops scoring this shard's cells once its *local* running top-k
    has been stable for ``patience`` consecutive scored cells. Stability and
    the ``probed`` ledger only advance on ranks the shard actually scores
    (local hits form a prefix after the stable sort, so a foreign rank can
    never retire a query early).

    Returns replicated ``(vals, ids, probed)`` with ``probed`` (b,) int32 =
    cells scored summed across shards — at full probe with no exits that is
    exactly ``nprobe`` (every cell is owned once). Merge is the canonical
    (value desc, id asc) cross-shard merge; with ``patience >= nprobe`` the
    result matches single-device ``search_early_exit`` on tie-free data
    (same ``_gathered_sims`` scorer — parity-tested), and early exits trade
    recall exactly like a smaller nprobe, which the serving SLO escalation
    already measures.
    """
    if index.is_compact:
        index = index.to_full()
    s = cf_shard_count(mesh, axes)
    c, cap = index.n_clusters, index.capacity
    c_ps = c // s
    nprobe = min(max(nprobe, 1), c)
    patience = max(int(patience), 1)
    full = nprobe >= c
    budget = c_ps if full else min(local_budget or nprobe, nprobe)
    b = queries.shape[0]
    q = queries.astype(jnp.float32)
    sids = (self_ids.astype(jnp.int32) if self_ids is not None
            else jnp.full((b,), -1, jnp.int32))
    csims = dense_similarity(q, index.centroids, measure)
    _, probe = jax.lax.top_k(csims, nprobe)  # (b, nprobe) replicated
    probe = probe.astype(jnp.int32)
    slot = jnp.arange(cap)
    opt_scale = [index.scale] if index.scale is not None else []
    opt_tomb = [tomb] if tomb is not None else []

    def inner(q, probe, sids, lists_l, rows_l, scale_l, fill, tomb_r):
        lin = shard_linear_index(mesh, axes)
        scale_l = scale_l[0] if scale_l else None
        tomb_r = tomb_r[0] if tomb_r else None
        local = (probe // c_ps) == lin
        order = jnp.argsort(~local, axis=1)  # stable: local hits lead
        pr = jnp.take_along_axis(probe, order, axis=1)[:, :budget]
        ok = jnp.take_along_axis(local, order, axis=1)[:, :budget]

        def step(carry, xs):
            vals, ids, stable, probed, active = carry
            prr, okr = xs  # (b,) global cell + is-local at this local rank
            score = active & okr
            lc = jnp.where(okr, prr - lin * c_ps, 0)
            rows = dequantize_payload(
                rows_l[lc],  # (b, cap, n) — one local cell per query
                None if scale_l is None else scale_l[lc])
            cc = lists_l[lc].astype(jnp.int32)
            live = slot[None, :] < fill[jnp.clip(prr, 0, c - 1)][:, None]
            sims = _gathered_sims(q, rows, measure)
            bad = ~live | (cc == sids[:, None]) | ~score[:, None]
            if tomb_r is not None:
                bad = bad | (live & tomb_r[cc])
            sims = jnp.where(bad, -jnp.inf, sims)
            mv, mi = _padded_topk(jnp.concatenate([vals, sims], axis=1),
                                  jnp.concatenate([ids, cc], axis=1), k)
            changed = jnp.any((mv != vals) | (mi != ids), axis=1)
            stable = jnp.where(changed, 0,
                               stable + score.astype(jnp.int32))
            probed = probed + score.astype(jnp.int32)
            active = active & (stable < patience)
            return (mv, mi, stable, probed, active), None

        init = (jnp.full((b, k), -jnp.inf),
                jnp.zeros((b, k), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.ones((b,), bool))
        (lv, li, _, probed, _), _ = jax.lax.scan(step, init, (pr.T, ok.T))
        li = jnp.where(jnp.isneginf(lv), INT_MAX, li)

        # the only request-path collectives: (b,) counts + (b, k) lists
        probed = jax.lax.psum(probed, axes)
        av = jax.lax.all_gather(lv, axes)
        ai = jax.lax.all_gather(li, axes)
        mv, mi = _canon_topk(
            jnp.moveaxis(av, 0, 1).reshape(b, -1),
            jnp.moveaxis(ai, 0, 1).reshape(b, -1), k)
        return mv, jnp.where(jnp.isneginf(mv), 0, mi), probed

    row2, row3 = P(axes, None), P(axes, None, None)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(None), row2, row3,
                  [row2] * len(opt_scale), P(None),
                  [P(None)] * len(opt_tomb)),
        out_specs=(P(None, None), P(None, None), P(None)),
        check_vma=False,
    )(q, probe, sids, index.lists, index.rows, opt_scale, index.fill,
      opt_tomb)
