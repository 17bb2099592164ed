"""One benchmark per paper table/figure family, on statistics-matched
synthetic data (raw MovieLens/Netflix are not redistributable here; see
DESIGN.md §8). Each function returns rows of dicts and is invoked by
``benchmarks.run``.

  fig2_mae_vs_landmarks     — Fig. 2/3: MAE per #landmarks × strategy (+ baseline)
  tab2_sim_combos           — Tables 2-5: MAE per (d1, d2) measure combo
  tab6_runtime_vs_landmarks — Tables 6-9: fit runtime per #landmarks × strategy
  tab10_baseline_runtime    — Table 10: full-matrix kNN runtime
  tab15_comparative         — Table 15: how many × slower each algorithm is
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import (
    BPMFConfig,
    fit_mf,
    fit_predict_bpmf,
    irsvd_config,
    pmf_config,
    predict_mf,
    rsvd_config,
    svdpp_config,
)
from repro.core import LandmarkSpec, fit, fit_baseline, predict
from repro.data.ratings import kfold_split, mae, synthesize

STRATEGIES = ("random", "dist_ratings", "coresets", "coresets_random", "popularity")


def _eval_landmark(data, tr, te, spec: LandmarkSpec, key=0):
    m = data.to_matrix(tr)
    jax.block_until_ready(fit(jax.random.PRNGKey(key), m, spec))  # warm jit
    t0 = time.perf_counter()
    st = fit(jax.random.PRNGKey(key), m, spec)
    jax.block_until_ready(st)
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    preds = predict(st, jnp.asarray(data.users[te]), jnp.asarray(data.items[te]), spec)
    preds.block_until_ready()
    t_pred = time.perf_counter() - t0
    return mae(np.asarray(preds), data.ratings[te]), t_fit, t_pred


def _eval_baseline(data, tr, te, measure, mode="user"):
    m = data.to_matrix(tr)
    spec = LandmarkSpec(mode=mode)
    fit_baseline(m, measure, mode).sims.block_until_ready()  # warm jit
    t0 = time.perf_counter()
    st = fit_baseline(m, measure, mode)
    st.sims.block_until_ready()
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    preds = predict(st, jnp.asarray(data.users[te]), jnp.asarray(data.items[te]), spec)
    preds.block_until_ready()
    return mae(np.asarray(preds), data.ratings[te]), t_fit, time.perf_counter() - t0


def fig2_mae_vs_landmarks(dataset="movielens100k", landmarks=(10, 40, 70, 100),
                          folds=2, mode="user") -> List[Dict]:
    data = synthesize(dataset, seed=0)
    rows = []
    for strategy in STRATEGIES:
        for n in landmarks:
            errs = []
            for f in range(folds):
                tr, te = kfold_split(data, f)
                spec = LandmarkSpec(n_landmarks=n, selection=strategy,
                                    d1="euclidean", d2="cosine", mode=mode)
                e, *_ = _eval_landmark(data, tr, te, spec, key=f)
                errs.append(e)
            rows.append({"dataset": dataset, "strategy": strategy, "n": n,
                         "mae": float(np.mean(errs))})
    # baseline CF cosine (the horizontal line in Fig. 2)
    errs = []
    for f in range(folds):
        tr, te = kfold_split(data, f)
        e, *_ = _eval_baseline(data, tr, te, "cosine", mode)
        errs.append(e)
    rows.append({"dataset": dataset, "strategy": "BASELINE_CF", "n": 0,
                 "mae": float(np.mean(errs))})
    return rows


def tab2_sim_combos(dataset="movielens100k", n=20, strategy="popularity") -> List[Dict]:
    data = synthesize(dataset, seed=0)
    tr, te = kfold_split(data, 0)
    rows = []
    for d1 in ("euclidean", "cosine", "pearson"):
        for d2 in ("euclidean", "cosine", "pearson"):
            spec = LandmarkSpec(n_landmarks=n, selection=strategy, d1=d1, d2=d2)
            e, t_fit, t_pred = _eval_landmark(data, tr, te, spec)
            rows.append({"dataset": dataset, "d1": d1, "d2": d2, "mae": e,
                         "fit_s": t_fit, "pred_s": t_pred})
    return rows


def tab6_runtime_vs_landmarks(dataset="movielens100k",
                              landmarks=(10, 40, 70, 100)) -> List[Dict]:
    data = synthesize(dataset, seed=0)
    tr, te = kfold_split(data, 0)
    rows = []
    for strategy in STRATEGIES:
        for n in landmarks:
            spec = LandmarkSpec(n_landmarks=n, selection=strategy)
            _, t_fit, t_pred = _eval_landmark(data, tr, te, spec)
            rows.append({"dataset": dataset, "strategy": strategy, "n": n,
                         "fit_s": t_fit, "pred_s": t_pred,
                         "total_s": t_fit + t_pred})
    return rows


def tab10_baseline_runtime(dataset="movielens100k") -> List[Dict]:
    data = synthesize(dataset, seed=0)
    tr, te = kfold_split(data, 0)
    rows = []
    for mode in ("user", "item"):
        e, t_fit, t_pred = _eval_baseline(data, tr, te, "cosine", mode)
        rows.append({"dataset": dataset, "mode": mode, "mae": e,
                     "total_s": t_fit + t_pred})
    return rows


def tab15_comparative(dataset="movielens100k", epochs=15) -> List[Dict]:
    """Relative runtime vs Landmarks kNN (paper's bold row == 1.0)."""
    data = synthesize(dataset, seed=0)
    tr, te = kfold_split(data, 0)
    rows = []

    spec = LandmarkSpec(n_landmarks=20, selection="popularity")
    lm_mae, t_fit, t_pred = _eval_landmark(data, tr, te, spec)
    t_lm = t_fit + t_pred
    rows.append({"algo": "Landmarks kNN", "mae": lm_mae, "time_s": t_lm, "rel": 1.0})

    for meas in ("euclidean", "cosine", "pearson"):
        e, tf, tp = _eval_baseline(data, tr, te, meas)
        rows.append({"algo": f"{meas} kNN", "mae": e, "time_s": tf + tp,
                     "rel": (tf + tp) / t_lm})

    for name, cfgf in (("RSVD", rsvd_config), ("IRSVD", irsvd_config),
                       ("PMF", pmf_config), ("SVD++", svdpp_config)):
        cfg = cfgf(data.n_users, data.n_items, epochs=epochs)
        t0 = time.perf_counter()
        params, aux = fit_mf(data.users[tr], data.items[tr], data.ratings[tr], cfg)
        preds = np.clip(np.asarray(
            predict_mf(params, cfg, data.users[te], data.items[te], aux)), 1, 5)
        dt = time.perf_counter() - t0
        rows.append({"algo": name, "mae": mae(preds, data.ratings[te]),
                     "time_s": dt, "rel": dt / t_lm})

    t0 = time.perf_counter()
    bcfg = BPMFConfig(data.n_users, data.n_items, n_samples=10, burnin=4)
    preds = fit_predict_bpmf(data.users[tr], data.items[tr], data.ratings[tr],
                             data.users[te], data.items[te], bcfg)
    dt = time.perf_counter() - t0
    rows.append({"algo": "BPMF", "mae": mae(np.asarray(preds), data.ratings[te]),
                 "time_s": dt, "rel": dt / t_lm})
    return rows


def graph_vs_dense_fit_bench(n_users=8192, n_items=512, n_lm=32, iters=2) -> List[Dict]:
    """Beyond-paper: the O(U²)→O(U·k) fit-artifact win of the NeighborGraph
    refactor, tracked per-commit in BENCH_*.json. Compares the dense-d2 fit
    (``dense_sims=True`` escape hatch) against the default graph fit on the
    same synthetic block: wall time + fitted-artifact bytes (+ XLA's peak
    temp-memory estimate where the backend reports one)."""
    rng = np.random.default_rng(0)
    r = rng.integers(1, 6, (n_users, n_items)).astype(np.float32)
    r *= rng.random((n_users, n_items)) < 0.05
    from repro.core import RatingMatrix

    m = RatingMatrix(jnp.asarray(r), n_users, n_items)
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    key = jax.random.PRNGKey(0)
    rows = []
    for variant, dense in (("dense_d2", True), ("graph", False)):
        fn = lambda: fit(key, m, spec, dense_sims=dense)
        jax.block_until_ready(fn())  # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            st = fn()
        jax.block_until_ready(st)
        dt = (time.perf_counter() - t0) / iters
        if dense:
            artifact = int(st.sims.nbytes)
        else:
            artifact = int(st.graph.indices.nbytes + st.graph.weights.nbytes)
        peak = None
        try:  # XLA estimate: transients + fitted output for the jitted fit
            mem = jax.jit(
                lambda k_, r_: fit(k_, RatingMatrix(r_, n_users, n_items),
                                   spec, dense_sims=dense)
            ).lower(key, m.ratings).compile().memory_analysis()
            peak = int(mem.temp_size_in_bytes) + int(mem.output_size_in_bytes)
        except Exception:  # memory_analysis availability varies by backend
            pass
        rows.append({"variant": variant, "fit_s": dt,
                     "artifact_bytes": artifact, "peak_bytes": peak})
    return rows


def foldin_vs_refit_bench(n_users=8192, n_items=512, batch=64, n_lm=32,
                          iters=3) -> List[Dict]:
    """Beyond-paper: the serve-path fold-in win — appending a ``batch`` of new
    users to a fitted state (O(b·n·P) d1 + new-vs-all scan + back-patch)
    versus the full refit the frozen artifact used to force. Both warm-jitted;
    wall time per update."""
    from repro.core import RatingMatrix, fold_in

    rng = np.random.default_rng(0)
    r = rng.integers(1, 6, (n_users + batch, n_items)).astype(np.float32)
    r *= rng.random((n_users + batch, n_items)) < 0.05
    r = jnp.asarray(r)
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    key = jax.random.PRNGKey(0)
    st = fit(key, RatingMatrix(r[:n_users], n_users, n_items), spec)
    jax.block_until_ready(st.graph.weights)

    rows = []
    new = r[n_users:]
    fi = lambda: fold_in(st, new, spec)
    refit = lambda: fit(key, RatingMatrix(r, n_users + batch, n_items), spec)
    for variant, fn in (("fold_in", fi), ("refit", refit)):
        jax.block_until_ready(fn().graph.weights)  # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out.graph.weights)
        rows.append({"variant": variant,
                     "update_s": (time.perf_counter() - t0) / iters})
    return rows


def decremental_vs_refit_bench(n_users=8192, n_items=512, batch=8, n_lm=32,
                               bq=128, iters=3) -> List[Dict]:
    """Beyond-paper: the write-path win — replacing ``batch`` users' rating
    rows in place (frozen-landmark re-projection + decremental neighbor-graph
    repair of every victim row, ``repro.mutation``) versus the synchronous
    from-scratch refit a mutation used to force. Both warm-jitted; wall time
    per mutation batch. ``batch=8`` is the engine write lane's minimum
    padded shape (``_mutation_shape``, lo=8) — the steady-state online
    write; ``bq`` covers the resulting ~``batch·(k+1)`` dirty rows in one
    repair call. The patched state is oracle-exact (bitwise) against the
    refit graph with the same landmarks — asserted by
    tests/test_mutation.py, so this row only has to carry the timing."""
    from repro import mutation
    from repro.core import RatingMatrix

    rng = np.random.default_rng(0)
    r = rng.integers(1, 6, (n_users, n_items)).astype(np.float32)
    r *= rng.random((n_users, n_items)) < 0.05
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    key = jax.random.PRNGKey(0)
    st = fit(key, RatingMatrix(jnp.asarray(r), n_users, n_items), spec)
    jax.block_until_ready(st.graph.weights)
    mst = mutation.from_fitted(st)

    ids = rng.choice(n_users, batch, replace=False).astype(np.int32)
    rows = rng.integers(1, 6, (batch, n_items)).astype(np.float32)
    rows *= rng.random((batch, n_items)) < 0.05
    jids, jrows = jnp.asarray(ids), jnp.asarray(rows)
    bv = jnp.int32(batch)

    def patch():
        out = mutation.update_ratings(mst, jids, jrows, bv, spec)
        return mutation.drain_repairs(out, spec, bq)

    rm = r.copy()
    rm[ids] = rows
    refit = lambda: fit(key, RatingMatrix(jnp.asarray(rm), n_users, n_items),
                        spec)

    out = []
    for variant, fn in (("patch_repair", patch), ("refit", refit)):
        w = fn()  # compile + warm
        jax.block_until_ready(
            w.bstate.state.graph.weights if variant == "patch_repair"
            else w.graph.weights)
        t0 = time.perf_counter()
        for _ in range(iters):
            w = fn()
            if variant == "patch_repair":
                jax.block_until_ready(w.bstate.state.graph.weights)
            else:
                jax.block_until_ready(w.graph.weights)
        out.append({"variant": variant, "b": batch, "u": n_users,
                    "update_s": (time.perf_counter() - t0) / iters})
    return out


def refresh_vs_refit_bench(u0=1024, n_items=192, waves=6, arrivals=128,
                           n_lm=16, requests=12, req_batch=256) -> List[Dict]:
    """Beyond-paper: steady-state serving with a *background* landmark refresh
    vs. naive synchronous refit-on-drift, on the same drifting arrival stream.

    Both variants serve `requests` warm bucketed pair-prediction calls per
    wave and fold arrivals in between; at the midpoint wave they rebuild the
    artifact on the accumulated matrix. ``background`` refits on a daemon
    thread (RefreshManager) while requests keep flowing; ``sync`` blocks the
    request loop on an in-process fit. Reported per variant: total wall-clock,
    worst-case single-request latency across the whole replay, and the number
    of executables compiled per bucketed request step (== buckets used when
    padding works).
    """
    import tempfile

    from repro.data.synthetic import drifting_ratings
    from repro.core import RatingMatrix, knn
    from repro.lifecycle import buckets
    from repro.lifecycle.refresh import RefreshManager

    spec = LandmarkSpec(n_landmarks=n_lm, selection="coresets")
    stream = dict(n_waves=waves, drift=1.0)
    rng = np.random.default_rng(0)
    rows = []
    # sync runs first and eats the one-time jit compiles — the cold refit IS
    # what a naive refit-on-drift deployment pays; background then measures
    # the steady state (its refit thread re-hits the same warm executables).
    for variant in ("sync", "background"):
        r0 = drifting_ratings(0, 0, u0, n_items, **stream)
        st = fit(jax.random.PRNGKey(0),
                 RatingMatrix(jnp.asarray(r0), u0, n_items), spec)
        jax.block_until_ready(st.graph.weights)
        bst = buckets.from_state(st, min_bucket=u0)
        manager = RefreshManager(tempfile.mkdtemp(prefix="cf_bench_"), spec)
        caps = {bst.capacity}
        pair_cache0 = knn.predict_pairs_graph._cache_size()
        worst = 0.0
        t_start = time.perf_counter()

        def apply_swap_if_committed():
            nonlocal bst
            done = manager.poll()
            if done is None:
                return
            _, st = done
            snap_u = st.ratings.shape[0]
            delta = bst.host_ratings(snap_u, int(bst.n_valid))
            bst = buckets.fold_in_rows(buckets.from_state(st, min_bucket=u0),
                                       delta, arrivals, spec, min_bucket=u0)
            caps.add(bst.capacity)
        for wave in range(waves):
            users = jnp.asarray(rng.integers(0, int(bst.n_valid),
                                             req_batch).astype(np.int32))
            items = jnp.asarray(rng.integers(0, n_items,
                                             req_batch).astype(np.int32))
            jax.block_until_ready(buckets.predict_pairs(bst, users, items))
            for _ in range(requests):
                t0 = time.perf_counter()
                jax.block_until_ready(buckets.predict_pairs(bst, users, items))
                worst = max(worst, time.perf_counter() - t0)
            if wave == waves // 2:  # drift point: rebuild the artifact
                acc = bst.host_ratings(0, int(bst.n_valid))
                if variant == "background":
                    manager.request(acc, generation=1)
                else:
                    t0 = time.perf_counter()
                    st = fit(jax.random.PRNGKey(1),
                             RatingMatrix(jnp.asarray(acc), *acc.shape), spec)
                    jax.block_until_ready(st.graph.weights)
                    # the refit blocks the request loop: it IS a request gap
                    worst = max(worst, time.perf_counter() - t0)
                    bst = buckets.from_state(st, min_bucket=u0)
                    caps.add(bst.capacity)
            if variant == "background":
                apply_swap_if_committed()
            if wave + 1 < waves:
                arr = drifting_ratings(0, wave + 1, arrivals, n_items, **stream)
                bst = buckets.fold_in_rows(bst, arr, arrivals, spec,
                                           min_bucket=u0)
                caps.add(bst.capacity)
        # a refit that outlasts the replay still commits and swaps on the
        # clock — the background variant must not silently drop its own work
        manager.join()
        apply_swap_if_committed()
        rows.append({
            "variant": variant,
            "wall_s": time.perf_counter() - t_start,
            "worst_request_s": worst,
            "buckets": len(caps),
            "pair_executables": knn.predict_pairs_graph._cache_size() - pair_cache0,
        })
    return rows


def sharded_foldin_vs_single_bench(u0=2048, n_items=256, batch=64, n_lm=16,
                                   iters=3) -> List[Dict]:
    """Beyond-paper: the mesh-sharded serve fold-in
    (``core.fold_in_sharded``: shard-local append + O(b·k·S) candidate-list
    all-gather) vs the single-device bucketed fold-in on the same state.
    Requires a multi-device runtime (CI forces 8 host-platform devices);
    returns [] on one device so ``benchmarks.run`` can report the skip.

    Both paths are warm-jitted and produce bit-identical predictions (the
    mesh-serving acceptance); what this row tracks is the *per-update wall
    time* and the per-shard padded footprint, so a regression in the
    shard_map schedule (e.g. an accidental all-gather of the representation)
    shows up as a step change.
    """
    import jax

    if jax.device_count() < 2:
        return []
    import jax.numpy as jnp

    from repro.core import RatingMatrix
    from repro.core.landmark_cf import fit
    from repro.lifecycle import buckets

    s = min(jax.device_count(), 8)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:s]).reshape(s),
                             ("data",))
    rng = np.random.default_rng(0)
    r = rng.integers(1, 6, (u0 + batch, n_items)).astype(np.float32)
    r *= rng.random((u0 + batch, n_items)) < 0.05
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0),
             RatingMatrix(jnp.asarray(r[:u0]), u0, n_items), spec)
    new = r[u0:]
    rows = []
    # min_bucket leaves headroom for the batch so the timed loop never grows
    # a bucket — otherwise the row would measure capacity-regrow repacking
    # (host round-trips on the sharded path) instead of the fold schedule
    mb_sh = max(8, u0 // s + batch)
    mb_si = u0 + batch
    for variant in ("single", "sharded"):
        if variant == "sharded":
            fresh = lambda: buckets.from_state_sharded(
                st, mesh, row_axes=("data",), min_bucket=mb_sh)
            fold = lambda state: buckets.fold_in_rows_sharded(
                state, new, batch, spec, min_bucket=mb_sh)[0]
        else:
            fresh = lambda: buckets.from_state(st, min_bucket=mb_si)
            fold = lambda state: buckets.fold_in_rows(state, new, batch, spec,
                                                      min_bucket=mb_si)
        warm = fresh()
        cap = warm.capacity * (s if variant == "sharded" else 1)
        jax.block_until_ready(fold(warm).state.graph.weights)  # warm jit
        states = [fresh() for _ in range(iters)]  # donation consumes inputs
        t0 = time.perf_counter()
        for state in states:
            out = fold(state)
        jax.block_until_ready(out.state.graph.weights)
        rows.append({"variant": variant, "devices": s if variant == "sharded"
                     else 1, "update_s": (time.perf_counter() - t0) / iters,
                     "capacity": cap})
    return rows


def engine_vs_waves_bench(u0=2048, n_items=256, n_lm=16, duration=5.0,
                          seed=0) -> List[Dict]:
    """Beyond-paper: the request-path serving engine (continuous
    micro-batching over the warm bucketed executables, async fold lane) vs
    the synchronous wave treatment (one padded jitted call per request,
    each waiting for the previous) on the same offered traffic.

    Three measurements on one fitted state:
      1. closed-loop sync baseline — per-request padded solo calls; its
         mean service time anchors both the sync capacity and the offered
         open-loop rate (2.6x capacity, i.e. deliberately past what the
         wave loop can absorb);
      2. the engine under that open-loop Poisson stream with two fold-in
         writes mixed in — sustained QPS, p50/p95/p99, shed fraction, and
         a bitwise solo-replay audit of the micro-batched results;
      3. the same offered arrival process replayed through the
         single-server wave queue (finish_i = max(arrive_i, finish_{i-1})
         + service) — what the sync loop's p95 degrades to at the rate the
         engine actually held.
    """
    from repro.core import RatingMatrix
    from repro.lifecycle import buckets
    from repro.serving import (EngineConfig, LocalBackend, RequestEngine,
                               latency_stats)

    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u0, n_items)).astype(np.float32)
    r *= rng.random((u0, n_items)) < 0.05
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0),
             RatingMatrix(jnp.asarray(r), u0, n_items), spec)
    jax.block_until_ready(st.graph.weights)
    cfg = EngineConfig(max_batch=128, min_shape=16, queue_cap=1024,
                       max_wait_ms=2.0, slo_ms=250.0, fold_bq=32)
    # headroom so the two fold batches never regrow the bucket — the row
    # measures the batching schedule, not capacity repacking
    mb = u0 + 256
    backend = LocalBackend(buckets.from_state(st, min_bucket=mb), spec,
                           min_bucket=mb)
    pub = backend.snapshot()
    for shape in cfg.batch_shapes():  # warm every request-path executable
        z = np.zeros(shape, np.int64)
        jax.block_until_ready(backend.predict_pairs(pub, z, z))

    def draw_req():
        m = int(rng.integers(8, 33))
        return m, rng.integers(0, u0, m), rng.integers(0, n_items, m)

    svc = []
    for _ in range(48):
        m, uu, it = draw_req()
        up = np.zeros(cfg.pad_shape(m), np.int64)
        up[:m] = uu
        ip = np.zeros_like(up)
        ip[:m] = it
        t0 = time.perf_counter()
        jax.block_until_ready(backend.predict_pairs(pub, up, ip))
        svc.append(time.perf_counter() - t0)
    sync_qps = 1.0 / float(np.mean(svc))
    sync_stats = latency_stats(svc)

    rate = 2.6 * sync_qps
    fold_rows = (rng.integers(1, 6, (32, n_items)) *
                 (rng.random((32, n_items)) < 0.05)).astype(np.float32)
    eng = RequestEngine(backend, cfg, clock=time.perf_counter)
    eng.start()
    reqs, arrivals = [], []
    t_start = time.perf_counter()
    t_stop = t_start + duration
    next_arr, next_fold, folds_sent = t_start, t_start + duration / 3.0, 0
    while True:
        now = time.perf_counter()
        if now >= t_stop:
            break
        if now >= next_arr:
            m, uu, it = draw_req()
            arrivals.append(next_arr - t_start)
            rq = eng.submit("pair", users=uu, items=it)
            if rq is not None:
                reqs.append(rq)
            next_arr += rng.exponential(1.0 / rate)
            continue
        if folds_sent < 2 and now >= next_fold:
            eng.submit("fold", rows=fold_rows)
            folds_sent += 1
            next_fold += duration / 3.0
            continue
        time.sleep(min(0.0005, max(0.0, next_arr - now)))
    for rq in reqs:
        if not rq.done.wait(timeout=120.0):
            raise RuntimeError("admitted request never completed")
    t_last = max(rq.t_done for rq in reqs)
    eng.stop()
    for _ in range(8):  # bitwise audit vs solo execution, final generation
        m, uu, it = draw_req()
        eng.submit("pair", users=uu, items=it)
    eng.pump_reads()
    checked, bad = eng.verify_sample(limit=8)
    stats = eng.stats()
    engine_qps = stats["reads_completed"] / max(t_last - t_start, 1e-9)

    fin, lat = 0.0, []
    for j, ta in enumerate(arrivals):
        fin = max(ta, fin) + svc[j % len(svc)]
        lat.append(fin - ta)
    sync_loaded = latency_stats(lat)

    rl = stats["read_latency"]
    return [
        {"variant": "sync_waves", "qps": sync_qps,
         "p95_ms": sync_stats.p95_ms, "loaded_p95_ms": sync_loaded.p95_ms},
        {"variant": "engine", "qps": engine_qps, "u": u0,
         "p50_ms": rl.p50_ms, "p95_ms": rl.p95_ms, "p99_ms": rl.p99_ms,
         "shed_frac": stats["shed_frac"],
         "folds": stats["completed"]["fold"], "nonfinite": stats["nonfinite"],
         "bitwise": bool(checked > 0 and bad == 0)},
    ]


def obs_overhead_bench(u0=2048, n_items=256, n_lm=16, rounds=14,
                       bursts=16, burst=48, seed=0) -> List[Dict]:
    """Beyond-paper: cost of the observability layer on the engine's hot
    path — the zero-overhead-when-disabled claim, measured.

    ONE engine, obs armed at construction, with the tracer's ``active``
    flag toggled between closed-loop chunks: active chunks trace every
    request (sample_rate=1.0) and publish the registry once per burst (the
    serve loop's cadence), inactive chunks pay exactly the disabled
    configuration's single ``tracer.active`` attribute read. A single
    engine instance keeps both treatments on the same threads — two
    engines would measure thread placement and scheduler luck, which on a
    shared host swings more than the instrumentation itself. Every chunk
    drains completely before the flag flips (no mid-flight toggling).

    Noise control, each piece measured as necessary on a shared host:
    chunks are *paired* per round with the treatment order alternating
    (off→on, then on→off — slow drift cancels inside the pair), the
    reported ratio is the median of per-round paired ratios (one noisy
    chunk poisons one ratio, not a whole side's median), ``gc.collect()``
    runs before every timed chunk (collection debt accrued by one chunk's
    allocations cannot land in the next), and the buffer is sized so no
    chunk hits the drop path (dropping is cheaper than recording — a
    saturated buffer understates the overhead).

    The acceptance bar (gated in CI through BENCH_serving.json):
    instrumented QPS >= 0.95x uninstrumented.
    """
    import gc

    from repro import obs as obslib
    from repro.core import RatingMatrix
    from repro.lifecycle import buckets
    from repro.serving import EngineConfig, LocalBackend, RequestEngine

    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u0, n_items)).astype(np.float32)
    r *= rng.random((u0, n_items)) < 0.05
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0),
             RatingMatrix(jnp.asarray(r), u0, n_items), spec)
    jax.block_until_ready(st.graph.weights)
    cfg = EngineConfig(max_batch=128, min_shape=16, queue_cap=4096,
                       max_wait_ms=2.0, slo_ms=250.0, fold_bq=32)
    backend = LocalBackend(buckets.from_state(st, min_bucket=u0), spec,
                           min_bucket=u0)
    pub = backend.snapshot()
    for shape in cfg.batch_shapes():  # warm every request-path executable
        z = np.zeros(shape, np.int64)
        jax.block_until_ready(backend.predict_pairs(pub, z, z))

    o = obslib.Observability(sample_rate=1.0, seed=0, max_events=500_000)
    eng = RequestEngine(backend, cfg, clock=time.perf_counter, obs=o)
    eng.start()

    def chunk(on: bool) -> float:
        """Closed-loop QPS of ``bursts`` bursts of ``burst`` requests."""
        o.tracer.active = on
        gc.collect()
        # pre-existing objects (incl. the span buffer filled by earlier
        # chunks) leave the collector's working set: gen1/gen2 scans of
        # *prior* chunks' spans would otherwise bill earlier treatments'
        # allocations to whichever chunk the scan lands in
        gc.freeze()
        done, t0 = 0, time.perf_counter()
        for _ in range(bursts):
            reqs = []
            for _ in range(burst):
                m = int(rng.integers(8, 33))
                rq = eng.submit("pair", users=rng.integers(0, u0, m),
                                items=rng.integers(0, n_items, m))
                if rq is not None:
                    reqs.append(rq)
            for rq in reqs:
                if not rq.done.wait(timeout=120.0):
                    raise RuntimeError("request never completed")
            done += len(reqs)
            if on:  # the serve loop's periodic registry publish
                eng.publish_metrics()
        return done / max(time.perf_counter() - t0, 1e-9)

    chunk(False)  # throwaway per treatment: thread spin-up, cache warmth
    chunk(True)
    qps_off, qps_on, ratios = [], [], []
    for i in range(rounds):
        if i % 2 == 0:
            off = chunk(False)
            on = chunk(True)
        else:
            on = chunk(True)
            off = chunk(False)
        qps_off.append(off)
        qps_on.append(on)
        ratios.append(on / max(off, 1e-9))
    eng.stop()
    return [
        {"variant": "obs_off", "qps": float(np.median(qps_off)), "u": u0},
        {"variant": "obs_on", "qps": float(np.median(qps_on)), "u": u0,
         "ratio": float(np.median(ratios)),
         "spans": len(o.tracer.events()), "dropped": o.tracer.dropped,
         "sample_rate": 1.0},
    ]


def ivf_vs_streaming_bench(u=8192, n_items=512, batch=64, n_lm=32,
                           n_clusters=96, nprobe=8, n_groups=16,
                           iters=30) -> List[Dict]:
    """Beyond-paper: IVF candidate generation vs the streaming scan on the
    serve fold-in — the new-vs-all half of ``extend_neighbor_graph``, which
    scans all U rows of the landmark embedding per batch on the streaming
    backend and only the ``nprobe`` probed cells on the IVF backend
    (``repro.retrieval``, docs/retrieval.md).

    Data is the drifting lifecycle stream with ``n_groups`` preference
    clusters (clustered populations are what IVF is for; uniform-random
    ratings have no cell structure and understate recall — the group count
    scales with U, 16 taste groups at 8k users). Both paths are warm-jitted
    and timed *interleaved* (one call of each per loop iteration, medians
    compared) so machine-load drift hits both sides equally — the ratio is
    the stable quantity, the absolute times are not. recall@k of the IVF
    candidates vs the exact streaming top-k rides in the ivf row, as does
    the (untimed) index build.
    """
    from repro.core import RatingMatrix
    from repro.core.graph import _streaming_query_topk
    from repro.core.landmark_cf import fit
    from repro.core.similarity import masked_similarity
    from repro.data.synthetic import drifting_ratings
    from repro import retrieval as rt

    gen = dict(n_waves=4, drift=1.0, n_groups=n_groups)
    waves = [drifting_ratings(0, w, u // 4, n_items, **gen) for w in range(4)]
    r = jnp.asarray(np.concatenate(waves))
    newr = jnp.asarray(drifting_ratings(1, 3, batch, n_items, **gen))
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, u, n_items), spec)
    new_rep = masked_similarity(newr, r[st.landmark_idx], spec.d1)
    cand = jnp.concatenate([st.representation, new_rep])
    k = st.graph.k

    stream = jax.jit(lambda q, c: _streaming_query_topk(
        q, c, spec.d2, k, 4096, self_offset=u))
    vs, is_ = stream(new_rep, cand)

    cfg = rt.resolve_ivf(rt.IVFSpec(n_clusters=n_clusters, nprobe=nprobe,
                                    slack=1.0), u)
    t0 = time.perf_counter()
    index = rt.build_index(st.representation, cfg, spec.d2)
    jax.block_until_ready(index.lists)
    t_build = time.perf_counter() - t0
    # slack=1.0 packs the index exactly full — reserve room for the batch
    # (as extend_neighbor_graph does) or append would silently drop it and
    # the row would measure a corrupted index
    need = -(-(u + batch) // cfg.n_clusters)  # ceil rows-per-list
    index = rt.grow_capacity(index, -(-need // 8) * 8)
    index = rt.append(index, new_rep, u + jnp.arange(batch), spec.d2)
    assert int(np.asarray(index.fill).sum()) == u + batch, "batch was dropped"
    self_ids = u + jnp.arange(batch)
    ivf = lambda: rt.search(index, new_rep, k, cfg.nprobe, spec.d2,
                            self_ids=self_ids)
    jax.block_until_ready(stream(new_rep, cand))  # warm both executables
    jax.block_until_ready(ivf())
    ts_stream, ts_ivf = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(stream(new_rep, cand))
        t1 = time.perf_counter()
        jax.block_until_ready(ivf())
        t2 = time.perf_counter()
        ts_stream.append(t1 - t0)
        ts_ivf.append(t2 - t1)
    t_stream = float(np.median(ts_stream))
    t_ivf = float(np.median(ts_ivf))
    va, ia = ivf()
    recall = float(rt.recall_at_k(ia, is_, va, vs))
    return [
        {"variant": "streaming", "search_s": t_stream, "recall": 1.0},
        {"variant": "ivf", "search_s": t_ivf, "recall": recall,
         "build_s": t_build, "n_clusters": cfg.n_clusters,
         "nprobe": cfg.nprobe, "capacity": index.capacity},
    ]


def kernel_fusion_bench(a=2048, p=4096, n=128, iters=3) -> List[Dict]:
    """Beyond-paper: fused-kernel schedule vs XLA multi-GEMM (wall time, CPU;
    the HBM-traffic model is the TPU story — see EXPERIMENTS.md §Perf)."""
    from repro.core.similarity import blocked_masked_similarity, masked_similarity

    rng = np.random.default_rng(0)
    r = rng.integers(1, 6, (a, p)).astype(np.float32) * (rng.random((a, p)) < 0.05)
    lm = r[:n]
    r, lm = jnp.asarray(r), jnp.asarray(lm)
    rows = []
    for name, fn in (("xla_multi_gemm", lambda: masked_similarity(r, lm, "cosine")),
                     ("streamed_schedule",
                      lambda: blocked_masked_similarity(r, lm, "cosine", chunk=1024))):
        fn()[0].block_until_ready()  # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        out.block_until_ready()
        rows.append({"variant": name, "us_per_call": (time.perf_counter() - t0) / iters * 1e6})
    return rows


def ivf_sharded_bench(scale="ci", batch=64, k=10, n=32,
                      iters=20) -> List[Dict]:
    """Tentpole row: probe-routed sharded IVF retrieval vs the streaming
    mesh scan (the new-vs-all phase of the sharded fold-in — every shard
    scores the replicated queries against ALL of its local rows, local
    top-k, one all-gather of the (b, k) lists, replicated merge).

    The population is a *synthesized* landmark-space embedding — a gaussian
    taste mixture (64 centers, noise 0.5), the geometry the d1 reduction
    produces — rather than a fitted one: a rating fit tops out around u=8k
    in bench time, and at that scale the all-rows scan is a single cheap
    GEMM per shard, so there is nothing for sublinear probing to win. The
    acceptance geometry (``scale="full"``: u=512k, C=2048, nprobe=32,
    budget=2*ceil(nprobe/S)) is where the committed >= 3x at recall@k
    >= 0.95 bar is measured (BENCH_retrieval.json); ``scale="ci"`` runs the
    same machinery at u=64k so a 2-core CI runner finishes the row in
    seconds — it tracks the plumbing, not the ratio. Both sides are
    warm-jitted and timed interleaved so machine-load drift cancels out of
    the ratio; returns [] on one device.
    """
    from functools import partial

    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.similarity import dense_similarity
    from repro import retrieval as rt

    if jax.device_count() < 2:
        return []
    u, n_clusters, nprobe, km_iters = {
        "ci": (65536, 1024, 16, 2),
        "full": (524288, 2048, 32, 4),
    }[scale]
    s = min(jax.device_count(), 8)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:s]).reshape(s),
                             ("data",))
    axes = ("data",)
    measure = "cosine"

    rng = np.random.default_rng(0)
    centers = rng.normal(size=(64, n)).astype(np.float32) * 3.0
    rep = jnp.asarray(centers[rng.integers(0, 64, u)]
                      + rng.normal(size=(u, n)).astype(np.float32) * 0.5)
    new_rep = jnp.asarray(centers[rng.integers(0, 64, batch)]
                          + rng.normal(size=(batch, n)).astype(np.float32)
                          * 0.5)
    total = u + batch
    self_ids = u + jnp.arange(batch, dtype=jnp.int32)

    # ---- baseline: streaming mesh scan (block-partitioned all-rows pass) --
    c_loc = -(-total // s)
    cand = jnp.pad(jnp.concatenate([rep, new_rep]),
                   ((0, s * c_loc - total), (0, 0)))
    cand = jax.device_put(cand, NamedSharding(mesh, P("data", None)))

    @jax.jit
    def mesh_stream(q, cand):
        def inner(q, c_l):
            lin = jax.lax.axis_index("data")
            gids = lin * c_loc + jnp.arange(c_loc, dtype=jnp.int32)
            sims = dense_similarity(q, c_l, measure)
            invalid = ((gids >= total)[None, :]
                       | (gids[None, :] == self_ids[:, None]))
            lv, li = jax.lax.top_k(jnp.where(invalid, -jnp.inf, sims), k)
            li = gids[li]
            av = jax.lax.all_gather(lv, "data")  # (S, b, k) — the only
            ai = jax.lax.all_gather(li, "data")  # request-path collective
            mv = jnp.moveaxis(av, 0, 1).reshape(batch, -1)
            mi = jnp.moveaxis(ai, 0, 1).reshape(batch, -1)
            nv, sel = jax.lax.top_k(mv, k)
            return nv, jnp.take_along_axis(mi, sel, axis=1)

        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None), P("data", None)),
                         out_specs=(P(None, None), P(None, None)),
                         check_vma=False)(q, cand)

    vs, is_ = mesh_stream(new_rep, cand)

    # ---- sharded IVF: build + append the batch, probe-routed search -------
    # spill_choices=4: the full preference order (the serving default) costs
    # a (u, C) full sort + C placement rounds at build — fine at serving C,
    # pointless at C=2048 where slack=1.25 makes deep spill unreachable
    cfg = rt.resolve_ivf_sharded(
        rt.IVFSpec(n_clusters=n_clusters, nprobe=nprobe, slack=1.25,
                   iters=km_iters, spill_choices=4), u, s)
    t0 = time.perf_counter()
    index = rt.build_index_sharded(rep, cfg, mesh, axes, measure)
    jax.block_until_ready(index.lists)
    t_build = time.perf_counter() - t0
    index, _ = rt.ensure_index_capacity_sharded(index, batch, mesh, axes)
    index = rt.append_sharded(index, new_rep, self_ids, mesh, axes, measure,
                              spill_choices=cfg.spill_choices)
    assert int(np.asarray(index.fill).sum()) == total, "batch dropped"
    budget = max(1, 2 * (-(-cfg.nprobe // s)))
    ivf = partial(rt.search_sharded, index, new_rep, k, cfg.nprobe, mesh,
                  axes, measure, self_ids=self_ids, local_budget=budget)
    jax.block_until_ready(mesh_stream(new_rep, cand))  # warm both
    jax.block_until_ready(ivf())
    ts_stream, ts_ivf = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(mesh_stream(new_rep, cand))
        t1 = time.perf_counter()
        jax.block_until_ready(ivf())
        t2 = time.perf_counter()
        ts_stream.append(t1 - t0)
        ts_ivf.append(t2 - t1)
    va, ia, probed = ivf()
    recall = float(rt.recall_at_k(ia, is_, va, vs))
    return [
        {"variant": "mesh_stream", "search_s": float(np.median(ts_stream)),
         "recall": 1.0, "devices": s, "u": u, "scale": scale},
        {"variant": "ivf_sharded", "search_s": float(np.median(ts_ivf)),
         "recall": recall, "build_s": t_build, "devices": s, "u": u,
         "scale": scale, "n_clusters": cfg.n_clusters, "nprobe": cfg.nprobe,
         "local_budget": budget, "capacity": index.capacity,
         "probed_per_query": float(np.mean(np.asarray(probed)))},
    ]


def fused_probe_bench(u=2048, n_items=256, n_lm=32, batch=32,
                      n_clusters=32, nprobe=4, iters=5) -> List[Dict]:
    """Fused Pallas probe kernel vs the gather/slice+GEMM jnp scorer on the
    same index. On CPU the kernel runs in interpret mode, so wall time there
    is a correctness exercise, not the perf story — the row's load-bearing
    fields are ``bitwise_full_probe`` (the kernel acceptance: identical to
    the exact GEMM at nprobe == C) and the TPU-side timing when available.
    """
    from repro.core import RatingMatrix
    from repro.core.landmark_cf import fit
    from repro.data.synthetic import drifting_ratings
    from repro import retrieval as rt

    r = jnp.asarray(drifting_ratings(0, 0, u, n_items, n_waves=1, drift=1.0))
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, u, n_items), spec)
    cfg = rt.resolve_ivf(rt.IVFSpec(n_clusters=n_clusters), u)
    index = rt.build_index(st.representation, cfg, spec.d2)
    q = st.representation[:batch]
    sid = jnp.arange(batch, dtype=jnp.int32)
    k = st.graph.k

    vj, ij = rt.search(index, q, k, cfg.n_clusters, spec.d2, self_ids=sid,
                       scorer="jnp")
    vf, if_ = rt.search(index, q, k, cfg.n_clusters, spec.d2, self_ids=sid,
                        scorer="fused")
    from repro.core.graph import finalize_topk
    gj, gf = finalize_topk(vj, ij), finalize_topk(vf, if_)
    bitwise = (np.array_equal(np.asarray(gj.indices), np.asarray(gf.indices))
               and np.array_equal(np.asarray(gj.weights),
                                  np.asarray(gf.weights)))
    rows = []
    for name in ("jnp", "fused"):
        fn = lambda: rt.search(index, q, k, nprobe, spec.d2, self_ids=sid,
                               scorer=name)
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        rows.append({"variant": name,
                     "search_s": (time.perf_counter() - t0) / iters,
                     "bitwise_full_probe": bitwise,
                     "backend": jax.default_backend()})
    return rows


def payload_quantization_bench(u=8192, n_items=512, n_lm=32, batch=64,
                               n_clusters=96, nprobe=8,
                               n_groups=4) -> List[Dict]:
    """Recall-vs-bandwidth curve of the quantized posting payloads: the same
    population indexed at f32 / bf16 / int8, recall@k at a fixed nprobe
    against the f32 full-probe exact reference, next to the resident posting
    bytes each variant streams per probe. f32 must stay exactly the f32
    index (``quantize_payload`` is the identity there) — asserted here, so
    the curve cannot silently shift its own baseline. The 4-group stream
    keeps recall off the 1.0 ceiling at this nprobe (the 16-group config
    saturates every dtype), so the rungs actually separate.
    """
    from repro.core import RatingMatrix
    from repro.core.landmark_cf import fit
    from repro.core.similarity import masked_similarity
    from repro.data.synthetic import drifting_ratings
    from repro import retrieval as rt

    gen = dict(n_waves=4, drift=1.0, n_groups=n_groups)
    waves = [drifting_ratings(0, w, u // 4, n_items, **gen) for w in range(4)]
    r = jnp.asarray(np.concatenate(waves))
    newr = jnp.asarray(drifting_ratings(1, 3, batch, n_items, **gen))
    spec = LandmarkSpec(n_landmarks=n_lm, selection="popularity")
    st = fit(jax.random.PRNGKey(0), RatingMatrix(r, u, n_items), spec)
    qrep = masked_similarity(newr, r[st.landmark_idx], spec.d1)
    k = st.graph.k

    base = rt.resolve_ivf(rt.IVFSpec(n_clusters=n_clusters, nprobe=nprobe), u)
    f32 = rt.build_index(st.representation, base, spec.d2)
    ve, ie = rt.search(f32, qrep, k, base.n_clusters, spec.d2)  # exact ref
    rows = []
    for dtype in ("f32", "bf16", "int8"):
        import dataclasses as _dc

        cfg = _dc.replace(base, payload_dtype=dtype)
        index = rt.build_index(st.representation, cfg, spec.d2)
        if dtype == "f32":
            np.testing.assert_array_equal(np.asarray(index.rows),
                                          np.asarray(f32.rows))
        va, ia = rt.search(index, qrep, k, nprobe, spec.d2)
        payload_bytes = index.rows.nbytes + (
            index.scale.nbytes if index.scale is not None else 0)
        rows.append({"variant": dtype,
                     "recall": float(rt.recall_at_k(ia, ie, va, ve)),
                     "payload_mb": payload_bytes / 2**20,
                     "nprobe": nprobe, "n_clusters": base.n_clusters})
    return rows
