#!/usr/bin/env python3
"""Run the landmark-CF serving path on a TPU and check it against NumPy.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the mesh path on four chips

The deployment is the paper's MovieLens-1M configuration (Table 1: 6,040
users x 3,952 items, 1M ratings, synthesized from ``--seed``) under the
registry's ``landmark_cf`` model: 20 popularity landmarks, cosine d1/d2,
k=13 neighbours.

One chip: the request-engine server of ``repro.launch.serve`` at that shape
(reads, fold-ins, updates, removals, IVF health probes), then fit ->
``RequestEngine`` reads -> fold-in -> update -> remove -> IVF build and
search through the public API, each answer compared with the plain NumPy
float64 reference below (independent of ``repro``).

Four chips: the same fitted state served by ``ShardedBackend`` and its
``shard_map`` router over a ``pod=4`` mesh, a sharded fold-in and a
full-probe ``search_sharded``, each compared with the single-device path of
the same process and with the reference.

Every check prints its observed maximum beside its limit. Any failed phase
exits non-zero. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Limits of the comparison with the float64 reference.
LIMITS = {
    "rep": 1e-4,   # d1 landmark representation, |delta|
    "weight": 1e-4,  # d2 neighbour weights, |delta|
    "pred": 1e-3,  # Eq. (1) predictions and top-N scores, |delta|
    "gap": 1e-5,   # neighbour ids must agree where w_k - w_(k+1) exceeds this
}
EPS = 1e-8  # the epsilon of the system's similarity and Eq. (1) denominators
# Request engine of both phases: pad shapes 32..256 rows, and a deadline and
# queue bound that never shed the synchronous pumps below.
ENGINE = dict(max_batch=256, min_shape=32, queue_cap=4096, fold_bq=64,
              topn=10, slo_ms=60_000.0)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ reference
def ref_representation(r: np.ndarray, lm: np.ndarray) -> np.ndarray:
    """d1 cosine over co-rated items, (U, n); < 2 co-rated items -> 0."""
    m, mm = (r != 0).astype(np.float64), (lm != 0).astype(np.float64)
    z = r @ lm.T
    x = (r * r) @ mm.T
    y = m @ (lm * lm).T
    c = m @ mm.T
    sim = z / np.maximum(np.sqrt(x) * np.sqrt(y), EPS)
    return np.where(c > 1, sim, 0.0)


def ref_topk(rep: np.ndarray, k: int, live: np.ndarray, rows: np.ndarray,
             block: int = 1024):
    """d2 cosine top-k of ``rows`` over live candidates, self excluded.

    Returns (ids, weights, w_next): lists in (weight desc, id asc) order and
    the (k+1)-th best weight of each row."""
    norm = np.sqrt((rep * rep).sum(1))
    ids = np.zeros((len(rows), k), np.int64)
    wts = np.zeros((len(rows), k))
    w_next = np.zeros(len(rows))
    for lo in range(0, len(rows), block):
        rb = rows[lo:lo + block]
        s = (rep[rb] @ rep.T) / np.maximum(norm[rb, None] * norm[None], EPS)
        s[:, ~live] = -np.inf
        s[np.arange(len(rb)), rb] = -np.inf
        part = np.argpartition(-s, k, axis=1)[:, :k + 1]
        pv = np.take_along_axis(s, part, 1)
        order = np.lexsort((part, -pv), axis=1)
        part = np.take_along_axis(part, order, 1)
        pv = np.take_along_axis(pv, order, 1)
        ids[lo:lo + block], wts[lo:lo + block] = part[:, :k], pv[:, :k]
        w_next[lo:lo + block] = pv[:, k]
    return ids, wts, w_next


def ref_sims(rep: np.ndarray, rows: np.ndarray, cols: np.ndarray):
    """d2 cosine of each row against its (B, k) listed columns."""
    a, b = rep[rows][:, None, :], rep[cols]
    na, nb = np.sqrt((a * a).sum(-1)), np.sqrt((b * b).sum(-1))
    return (a * b).sum(-1) / np.maximum(na * nb, EPS)


def ref_means(r: np.ndarray) -> np.ndarray:
    cnt = (r != 0).sum(1)
    return np.where(cnt > 0, r.sum(1) / np.maximum(cnt, 1), 0.0)


def ref_pairs(r, means, ids, wts, users, items):
    """Eq. (1) for (user, item) pairs over the given neighbour lists."""
    nb, w = ids[users], wts[users]
    rv = r[nb, items[:, None]]
    m = rv != 0
    num = (w * (rv - means[nb]) * m).sum(1)
    den = (np.abs(w) * m).sum(1)
    return means[users] + num / np.maximum(den, EPS)


def ref_scores(r, means, ids, wts, users):
    """Eq. (1) over every item for each user, (B, P)."""
    nb, w = ids[users], wts[users]
    rr = r[nb]
    m = rr != 0
    num = np.einsum("bk,bkp->bp", w, (rr - means[nb][..., None]) * m)
    den = np.einsum("bk,bkp->bp", np.abs(w), m)
    return means[users][:, None] + num / np.maximum(den, EPS)


class Checks:
    """Observed maxima against their limits; a failure fails the run."""

    def __init__(self):
        self.failed = []

    def le(self, name: str, value: float, limit: float) -> None:
        ok = bool(value <= limit)
        log(f"  check {name}: {value:.3e} <= {limit:.0e} "
            f"{'ok' if ok else 'FAILED'}")
        if not ok:
            self.failed.append(name)

    def true(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"  check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)

    def graph(self, name, rep, live, rows, got_ids, got_w):
        """Served neighbour lists of ``rows`` against the reference top-k.

        Ids: every listed neighbour is live, not the row itself, and within
        the gap limit of the reference k-th weight; every reference neighbour
        that beats the (k+1)-th by more than the gap limit is listed. Where
        w_k - w_(k+1) exceeds the limit this makes the sets identical.
        Weights: each listed weight against the reference similarity of that
        pair. Returns those reference similarities, (B, k)."""
        k = got_ids.shape[1]
        ref_ids, ref_w, w_next = ref_topk(rep, k, live, rows)
        tol = LIMITS["gap"]
        ok_id = ((got_ids >= 0) & (got_ids < len(rep))).all()
        got_ids = np.clip(got_ids, 0, len(rep) - 1)
        ok_id &= bool((live[got_ids] & (got_ids != rows[:, None])).all())
        sims = ref_sims(rep, rows, got_ids)
        below = float((ref_w[:, -1] - sims.min(1)).max())
        listed = (ref_ids[:, :, None] == got_ids[:, None, :]).any(-1)
        missing = int(((ref_w > w_next[:, None] + tol) & ~listed).sum())
        open_rows = int((ref_w[:, -1] - w_next <= tol).sum())
        self.true(f"{name} neighbour ids", bool(ok_id) and below <= tol
                  and missing == 0,
                  f"(worst listed weight {below:.2e} below the k-th; "
                  f"{missing} decided neighbours missing; {open_rows} of "
                  f"{len(rows)} rows tie within {tol:.0e} at the k-th)")
        self.le(f"{name} weights |delta|", float(np.abs(got_w - sims).max()),
                LIMITS["weight"])
        return sims


def top_n_error(items, scores, ref_sc, rated) -> float:
    """Largest score error of a served top-N list: the returned items'
    scores against the reference, and how far the best unreturned unrated
    item beats the list's last entry."""
    err = 0.0
    for it, sc, ref, seen in zip(items, scores, ref_sc, rated):
        live = it >= 0
        err = max(err, float(np.abs(sc[live] - ref[it[live]]).max(initial=0)))
        rest = ref.copy()
        rest[seen | np.isin(np.arange(len(ref)), it[live])] = -np.inf
        if live.all():
            err = max(err, float(rest.max()) - float(ref[it].min()))
    return err


# ------------------------------------------------------------------- chip
def compile_program(name: str, fn, *args):
    """AOT-compile ``fn``; report compile seconds and the Pallas call."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    custom = "tpu_custom_call" in compiled.as_text()
    log(f"program {name}: compile {dt:.2f}s, tpu_custom_call={custom}")
    return compiled, custom


def timed_run(name: str, compiled, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    log(f"program {name}: run {time.perf_counter() - t0:.3f}s")
    return out


def request_stream(rng, n_users: int, n_items: int, n_req: int):
    """(kind, users, items) requests of 4-16 rows, 15% top-N."""
    out = []
    for _ in range(n_req):
        m = int(rng.integers(4, 17))
        users = rng.integers(0, n_users, m)
        if rng.random() < 0.15:
            out.append(("topn", users, None))
        else:
            out.append(("pair", users, rng.integers(0, n_items, m)))
    return out


def new_users(r_np: np.ndarray, rng, m: int = 64) -> np.ndarray:
    """``m`` fold-in rows: existing users' rows with a fifth of ratings gone."""
    rows = r_np[rng.choice(len(r_np), m, replace=False)]
    return (rows * (rng.random(rows.shape) < 0.8)).astype(np.float32)


def serve_requests(eng, reqs, chunk: int = 64):
    """Submit through the engine's admission queue, drain, return results."""
    out = []
    for lo in range(0, len(reqs), chunk):
        got = [eng.submit(kind, users=u, items=i) for kind, u, i in
               reqs[lo:lo + chunk]]
        eng.pump_reads()
        if any(r is None for r in got):
            raise RuntimeError("engine shed a request below its queue bound")
        out += [r.result for r in got]
    return out


def check_reads(chk, name, reqs, results, r, means, nb_ids, nb_w):
    """Pair and top-N answers against Eq. (1) in float64, over the served
    neighbour lists ``nb_ids`` with their reference weights ``nb_w``."""
    pu, pi, pg = [], [], []
    tu, ti, ts = [], [], []
    for (kind, users, items), res in zip(reqs, results):
        if kind == "pair":
            pu.append(users), pi.append(items), pg.append(res)
        else:
            tu.append(users), ti.append(res[0]), ts.append(res[1])
    pu, pi, pg = map(np.concatenate, (pu, pi, pg))
    want = ref_pairs(r, means, nb_ids, nb_w, pu, pi)
    chk.true(f"{name} pair answers finite", bool(np.isfinite(pg).all()))
    chk.le(f"{name} pair |delta| ({len(pu)} pairs)",
           float(np.abs(pg - want).max()), LIMITS["pred"])
    tu, ti, ts = (np.concatenate(x)[:512] for x in (tu, ti, ts))
    err = 0.0
    for lo in range(0, len(tu), 128):
        sl = slice(lo, lo + 128)
        ref_sc = ref_scores(r, means, nb_ids, nb_w, tu[sl])
        err = max(err, top_n_error(ti[sl], ts[sl], ref_sc, r[tu[sl]] != 0))
    chk.le(f"{name} top-N score error ({len(tu)} lists)", err,
           LIMITS["pred"])


def one_chip(r_np: np.ndarray, seed: int):
    """The single-chip phases; returns the failed checks."""
    import jax
    import jax.numpy as jnp

    from repro import retrieval as rt
    from repro.configs import registry
    from repro.core import RatingMatrix, fit
    from repro.core.graph import build_neighbor_graph, resolve_backend
    from repro.launch import serve
    from repro.lifecycle import buckets
    from repro.retrieval.index import resolve_scorer
    from repro.retrieval.kmeans import resolve_assign_backend
    from repro.serving import EngineConfig, MutableLocalBackend, RequestEngine

    chk = Checks()
    spec = registry.get("landmark_cf").model
    u, p = r_np.shape
    k = spec.k_neighbors
    backends = {"graph": resolve_backend("auto", spec.d2),
                "assign": resolve_assign_backend("auto"),
                "scorer": resolve_scorer("auto")}
    log(f"resolved backends: {backends}")
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        chk.true("backends are the chip's", backends == {
            "graph": "pallas", "assign": "pallas", "scorer": "fused"})

    # -- 1. the server entry point, as a user runs it --------------------
    log("phase serve: repro.launch.serve --engine --mutations "
        "--retrieval ivf")
    t0 = time.perf_counter()
    serve.main(["--workload", "cf", "--engine", "--mutations",
                "--retrieval", "ivf", "--users", str(u), "--items", str(p),
                "--selection", spec.selection,
                "--duration", "4"])
    log(f"phase serve: done in {time.perf_counter() - t0:.1f}s")

    # -- 2. fit, compiled apart from its run ------------------------------
    key = jax.random.PRNGKey(seed)
    ratings = jnp.asarray(r_np, jnp.float32)
    fit_c, fit_tc = compile_program(
        "fit", lambda x: fit(key, RatingMatrix(x, u, p), spec), ratings)
    st = timed_run("fit", fit_c, ratings)
    graph_c, graph_tc = compile_program(
        "graph", lambda rep: build_neighbor_graph(rep, spec.d2, k),
        st.representation)
    g2 = timed_run("graph", graph_c, st.representation)
    chk.true("graph program equals the fit's graph",
             np.array_equal(np.asarray(g2.indices),
                            np.asarray(st.graph.indices)))

    # -- 3. the reference ------------------------------------------------
    t0 = time.perf_counter()
    r = r_np.astype(np.float64)
    lm_idx = np.asarray(st.landmark_idx)
    counts = (r != 0).sum(1)
    rest = np.delete(counts, lm_idx)
    chk.true("popularity landmarks", len(set(lm_idx.tolist())) == len(lm_idx)
             and counts[lm_idx].min() >= rest.max(),
             f"(least popular landmark {counts[lm_idx].min()} ratings, most "
             f"popular other user {rest.max()})")
    lm = r[lm_idx]
    rep = ref_representation(r, lm)
    live = np.ones(u, bool)
    means = ref_means(r)
    chk.le("d1 representation |delta|",
           float(np.abs(np.asarray(st.representation) - rep).max()),
           LIMITS["rep"])
    nb_ids = np.asarray(st.graph.indices)
    nb_w = chk.graph("fit graph", rep, live, np.arange(u), nb_ids,
                     np.asarray(st.graph.weights))
    log(f"reference: U={u} P={p} n={len(lm_idx)} k={k} in "
        f"{time.perf_counter() - t0:.1f}s")

    # -- 4. engine reads --------------------------------------------------
    cfg = EngineConfig(**ENGINE)
    bst = buckets.from_state(st)
    backend = MutableLocalBackend(bst, spec, warm_shapes=cfg.batch_shapes(),
                                  warm_topn=cfg.topn)
    eng = RequestEngine(backend, cfg)
    rng = np.random.default_rng(seed + 1)
    reqs = request_stream(rng, u, p, 3000)
    t0 = time.perf_counter()
    results = serve_requests(eng, reqs[:64])
    log(f"engine: first 64 requests (compiles) {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    results += serve_requests(eng, reqs[64:])
    dt = time.perf_counter() - t0
    log(f"engine: {len(reqs) - 64} requests in {dt:.2f}s "
        f"({eng.stats()['batches']} batches)")
    check_reads(chk, "engine", reqs, results, r, means, nb_ids, nb_w)

    # -- 5. the write lane: fold-in, update, removal ----------------------
    data_rng = np.random.default_rng(seed + 2)
    new_rows = new_users(r_np, data_rng)
    # updates and removals spare the landmark users: their rows are the
    # frozen projection basis until a refresh
    not_lm = np.setdiff1d(np.arange(u), lm_idx)
    touched = data_rng.choice(not_lm, 16, replace=False)
    upd_ids, rm_ids = touched[:8], touched[8:]
    upd_rows = r_np[data_rng.choice(u, 8)]
    t0 = time.perf_counter()
    for kind, kw in (("fold", dict(rows=new_rows)),
                     ("update", dict(users=upd_ids, rows=upd_rows)),
                     ("remove", dict(users=rm_ids))):
        if eng.submit(kind, **kw) is None:
            raise RuntimeError(f"write lane shed the {kind}")
    eng.pump_folds()
    log(f"write lane: fold 64 + update 8 + remove 8 in "
        f"{time.perf_counter() - t0:.2f}s, generation {backend.generation}")
    stats = eng.stats()["completed"]
    chk.true("write lane drained", stats["fold"] == 1 and stats["update"] == 1
             and stats["remove"] == 1, str(stats))

    r2 = np.concatenate([r, new_rows.astype(np.float64)])
    r2[upd_ids] = upd_rows
    r2[rm_ids] = 0.0
    u2 = len(r2)
    live2 = np.ones(u2, bool)
    live2[rm_ids] = False
    rep2 = ref_representation(r2, lm)  # the landmark basis stays frozen
    mst = backend.snapshot()[0]
    g = mst.bstate.state.graph
    readers = np.flatnonzero(live2)
    chk.le("write-lane representation |delta|", float(np.abs(
        np.asarray(mst.bstate.state.representation)[:u2] - rep2).max()),
        LIMITS["rep"])
    nb_ids2 = np.asarray(g.indices)[:u2]
    nb_w2 = np.zeros(nb_ids2.shape)
    nb_w2[readers] = chk.graph("write-lane graph", rep2, live2, readers,
                               nb_ids2[readers],
                               np.asarray(g.weights)[readers])
    reqs2 = [(kind, readers[users % len(readers)], items)
             for kind, users, items in request_stream(rng, u2, p, 400)]
    check_reads(chk, "post-write engine", reqs2, serve_requests(eng, reqs2),
                r2, ref_means(r2), nb_ids2, nb_w2)

    # -- 6. IVF index build + search --------------------------------------
    ivf = rt.resolve_ivf(rt.IVFSpec(), u)
    t0 = time.perf_counter()
    index = jax.block_until_ready(rt.build_index(st.representation, ivf,
                                                 spec.d2))
    log(f"ivf: build C={ivf.n_clusters} cap={index.capacity} in "
        f"{time.perf_counter() - t0:.2f}s (k-means assign "
        f"{backends['assign']})")
    q = jnp.asarray(rng.choice(u, 256, replace=False).astype(np.int32))
    qrep = st.representation[q]
    full_c, search_tc = compile_program(
        "search", lambda ix, x, s: rt.search(
            ix, x, k=k, nprobe=ivf.n_clusters, measure=spec.d2, self_ids=s,
            scorer="auto"), index, qrep, q)
    vf, idf = timed_run("search", full_c, index, qrep, q)
    qn = np.asarray(q)
    chk.graph("ivf full probe", rep, live, qn, np.asarray(idf),
              np.asarray(vf))
    vp, idp = rt.search(index, qrep, k=k, nprobe=ivf.nprobe,
                        measure=spec.d2, self_ids=q, scorer="auto")
    recall = float(rt.recall_at_k(idp, idf, vp, vf))
    log(f"ivf: recall@{k} at nprobe={ivf.nprobe}/{ivf.n_clusters} "
        f"= {recall:.4f} (reported, not gated)")

    if on_tpu:
        chk.true("tpu_custom_call in fit, graph and search programs",
                 fit_tc and graph_tc and search_tc)
    return chk.failed


def four_chips(r_np: np.ndarray, seed: int, devices):
    """The mesh phases on a ``pod=4`` mesh; returns the failed checks."""
    import jax
    import jax.numpy as jnp

    from repro import retrieval as rt
    from repro.configs import registry
    from repro.core import RatingMatrix, fit
    from repro.launch.mesh import make_mesh
    from repro.lifecycle import buckets
    from repro.serving import (EngineConfig, LocalBackend, RequestEngine,
                               ShardedBackend)

    chk = Checks()
    spec = registry.get("landmark_cf").model
    u, p = r_np.shape
    k = spec.k_neighbors
    mesh = make_mesh((len(devices),), ("pod",), devices=devices)
    axes = ("pod",)
    s = len(devices)

    t0 = time.perf_counter()
    st = fit(jax.random.PRNGKey(seed), RatingMatrix(jnp.asarray(r_np), u, p),
             spec)
    jax.block_until_ready(st.graph.weights)
    log(f"fit on one device: {time.perf_counter() - t0:.1f}s")
    r = r_np.astype(np.float64)
    lm = r[np.asarray(st.landmark_idx)]
    rep = ref_representation(r, lm)
    live = np.ones(u, bool)
    nb_ids = np.asarray(st.graph.indices)
    nb_w = chk.graph("fit graph", rep, live, np.arange(u), nb_ids,
                     np.asarray(st.graph.weights))
    means = ref_means(r)

    cfg = EngineConfig(**ENGINE)
    local = LocalBackend(buckets.from_state(st), spec,
                         warm_shapes=cfg.batch_shapes(), warm_topn=cfg.topn)
    min_shard = max(8, 256 // s)
    t0 = time.perf_counter()
    sst = buckets.from_state_sharded(st, mesh, axes, min_shard)
    u_per = -(-u // s)
    sharded = ShardedBackend(sst, np.arange(u) // u_per, np.arange(u) % u_per,
                             spec, min_bucket=min_shard,
                             warm_shapes=cfg.batch_shapes(),
                             warm_topn=cfg.topn)
    log(f"sharded state on {s} devices: {time.perf_counter() - t0:.1f}s")
    for name, arr in (("ratings", sst.state.ratings),
                      ("representation", sst.state.representation),
                      ("graph.indices", sst.state.graph.indices),
                      ("graph.weights", sst.state.graph.weights)):
        per = {str(sh.device.id): sh.data.nbytes
               for sh in arr.addressable_shards}
        log(f"per-device bytes {name}: {per}")
        chk.true(f"{name} rows spread over {s} devices",
                 len(per) == s and len(set(per.values())) == 1
                 and sum(per.values()) == arr.nbytes)

    rng = np.random.default_rng(seed + 1)
    reqs = request_stream(rng, u, p, 1500)
    eng_l, eng_s = RequestEngine(local, cfg), RequestEngine(sharded, cfg)
    t0 = time.perf_counter()
    got_s = serve_requests(eng_s, reqs)
    log(f"routed engine: {len(reqs)} requests in "
        f"{time.perf_counter() - t0:.2f}s (compiles included)")
    got_l = serve_requests(eng_l, reqs)
    check_reads(chk, "routed", reqs, got_s, r, means, nb_ids, nb_w)
    check_reads(chk, "single-device", reqs, got_l, r, means, nb_ids, nb_w)
    pair_d = [np.abs(a - b).max() for (kind, _, _), a, b in
              zip(reqs, got_s, got_l) if kind == "pair"]
    chk.le("routed vs single-device pair |delta|", float(max(pair_d)),
           LIMITS["pred"])

    new_rows = new_users(r_np, np.random.default_rng(seed + 2))
    t0 = time.perf_counter()
    for eng in (eng_s, eng_l):
        eng.submit("fold", rows=new_rows)
        eng.pump_folds()
    log(f"fold-in of 64 rows, sharded then single: "
        f"{time.perf_counter() - t0:.2f}s")
    r2 = np.concatenate([r, new_rows.astype(np.float64)])
    u2 = len(r2)
    rep2 = ref_representation(r2, lm)
    sst2, id_shard, id_slot, _ = sharded.snapshot()
    sid = id_shard * sst2.capacity + id_slot  # logical -> sharded row
    dense_of = np.full(sst2.state.ratings.shape[0], -1)
    dense_of[sid] = np.arange(u2)
    gi = dense_of[np.asarray(sst2.state.graph.indices)[sid]]
    nb_w2 = chk.graph("sharded fold-in graph", rep2, np.ones(u2, bool),
                      np.arange(u2), gi,
                      np.asarray(sst2.state.graph.weights)[sid])
    reqs2 = request_stream(rng, u2, p, 300)
    check_reads(chk, "routed post-fold", reqs2, serve_requests(eng_s, reqs2),
                r2, ref_means(r2), gi, nb_w2)

    ivf = rt.resolve_ivf_sharded(rt.IVFSpec(), u, s)
    index = rt.build_index_sharded(st.representation, ivf, mesh, axes,
                                   spec.d2)
    q = jnp.asarray(rng.choice(u, 256, replace=False).astype(np.int32))
    qrep = st.representation[q]
    t0 = time.perf_counter()
    vs, is_, _ = rt.search_sharded(index, qrep, k, ivf.n_clusters, mesh, axes,
                                   spec.d2, self_ids=q)
    jax.block_until_ready(vs)
    log(f"search_sharded full probe C={ivf.n_clusters}: "
        f"{time.perf_counter() - t0:.2f}s (compile included)")
    single = rt.build_index(st.representation, ivf, spec.d2)
    v1, i1 = rt.search(single, qrep, k=k, nprobe=ivf.n_clusters,
                       measure=spec.d2, self_ids=q)
    qn = np.asarray(q)
    chk.graph("search_sharded full probe", rep, live, qn, np.asarray(is_),
              np.asarray(vs))
    chk.graph("single-device search full probe", rep, live, qn,
              np.asarray(i1), np.asarray(v1))
    return chk.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path on four chips and the "
                    "single-device path it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthesized ratings")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this script runs only on the chip", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.data.ratings import synthesize
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    data = synthesize("movielens1m", args.seed)
    r_np = np.zeros((data.n_users, data.n_items), np.float32)
    r_np[data.users, data.items] = data.ratings
    log(f"data: movielens1m shape {r_np.shape}, {data.n_ratings} ratings, "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    if args.four_chips:
        failed = four_chips(r_np, args.seed, devices[:4])
    else:
        failed = one_chip(r_np, args.seed)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    log(f"phases: {time.perf_counter() - t0:.1f}s")
    if failed:
        print(f"chip_smoke: failed checks: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
