"""Serving launcher — mode-dispatched on ``--workload``:

- ``lm`` (default): prefill + batched decode with the exact or landmark KV
  path.  ``python -m repro.launch.serve --arch smollm-360m --smoke --tokens 16``
- ``cf``: the landmark-CF serve loop (docs/serving.md) — load a fitted
  ``LandmarkState`` artifact (fit + checkpoint one in-process when the
  directory is empty), run warm jitted ``predict_pairs_graph`` / top-N
  recommendation waves, and apply ``fold_in`` batches between waves.
  ``python -m repro.launch.serve --workload cf --smoke``
- ``cf --lifecycle``: the full continual-serving loop (docs/lifecycle.md) —
  replay a drifting arrival stream (``data.synthetic.drifting_ratings``)
  through bucket-padded executables (``repro.lifecycle.buckets``), online
  drift monitoring (holdout-MAE reservoir, fold-in volume, landmark
  coverage), and policy-triggered background landmark refresh with an atomic
  generation-stamped artifact swap.
  ``python -m repro.launch.serve --workload cf --lifecycle --smoke``
- ``cf --lifecycle --retrieval ivf``: same loop with the IVF retrieval
  sidecar (docs/retrieval.md) — an inverted-file index over the landmark
  embedding rides the artifact: fold-in appends arrivals under the frozen
  quantizer, the background refresh rebuilds it inside the swap, a list-skew
  hysteresis gate (``policy.should_rebalance``) repacks it proactively, and
  every wave reports recall@k of the default-nprobe search vs the exact
  path (asserted ≥ 0.95 under ``--smoke``).
  ``python -m repro.launch.serve --workload cf --lifecycle --smoke --retrieval ivf``
- ``cf --lifecycle --mesh pod=K,data=L``: the same loop sharded end-to-end
  (docs/distributed_serving.md) — ``fit_distributed`` base generation,
  ``ShardedLandmarkState`` serving with per-shard bucket capacities,
  shard-local-append fold-in, mesh-aware background refresh committing
  per-shard checkpoint files — with a single-device shadow replay asserting
  every wave's predictions are *bit-identical*, and a jaxpr/sharding check
  proving the fold-in path never materializes a replicated (U, n)
  representation. Under ``JAX_PLATFORMS=cpu`` the device count is forced
  to K·L host devices (CI runs exactly this):
  ``python -m repro.launch.serve --workload cf --lifecycle --smoke --mesh pod=2,data=4``
- ``cf --engine``: open-loop serving through the continuous micro-batching
  request engine (``repro.serving``, docs/serving.md) — a load generator
  drives mixed pair/top-N/fold-in traffic at a target arrival rate through
  a deadline-aware batch former, bounded admission queue and async fold-in
  lane; reports sustained QPS + p50/p95/p99 + shed rate. Under ``--mesh``
  the request path is the ``shard_map`` query router (owner-routed neighbor
  data, jaxpr-checked to materialize nothing population-sized):
  ``python -m repro.launch.serve --workload cf --engine --smoke --mesh pod=4``

CF latency is reported per wave as p50/p95/p99 over the timed request loop
(``serving.stats`` — the same helper the engine uses, so numbers compare
across modes). In
plain ``cf`` mode fold-in changes U, so the first request after it recompiles
the step and the wave loop re-warms before timing; ``--lifecycle`` is the
production answer — U (and the fold-in batch) are padded to a geometric bucket
schedule, so each jitted step compiles once per bucket and the replay reports
the recompile count to prove it.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obslib
from repro.configs import registry
from repro.data import synthetic as S
from repro.distributed.sharding import DEFAULT_RULES
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as lm_mod


# ------------------------------------------------------------------------- lm
def _serve_lm(args):
    arch = registry.get(args.arch)
    cfg = arch.smoke_model if args.smoke else arch.model
    params = lm_mod.init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jnp.asarray(
        S.lm_batch(0, 0, args.batch, args.prompt_len, cfg.vocab)["tokens"]
    )
    max_seq = args.prompt_len + args.tokens

    t0 = time.perf_counter()
    logits, cache = lm_mod.lm_prefill(params, prompts, cfg, DEFAULT_RULES,
                                      max_seq=max_seq)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.0f}ms")

    if args.landmark:
        lm_cache = lm_mod.make_landmark_cache(cfg, args.batch)
        lm_cache["k_lm"] = jax.random.normal(jax.random.PRNGKey(1),
                                             lm_cache["k_lm"].shape, cfg.dtype)
        lm_cache["q_lm"] = jax.random.normal(jax.random.PRNGKey(2),
                                             lm_cache["q_lm"].shape, cfg.dtype)
        step = jax.jit(lambda p, c, t: lm_mod.lm_landmark_decode_step(
            p, c, t, cfg, DEFAULT_RULES))
        cache = lm_cache
    else:
        step = jax.jit(lambda p, c, t: lm_mod.lm_decode_step(
            p, c, t, cfg, DEFAULT_RULES))

    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for _ in range(args.tokens):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    mode = "landmark O(n)" if args.landmark else "exact KV"
    print(f"decode {args.tokens} tokens ({mode}): "
          f"{dt/args.tokens*1e3:.1f} ms/token")
    print("sample ids:", np.asarray(jnp.concatenate(out_tokens, 1))[0][:12])


# ------------------------------------------------------------------------- cf
def _synth_ratings(rng, users, items, density=0.08):
    r = rng.integers(1, 6, (users, items)).astype(np.float32)
    r *= rng.random((users, items)) < density
    return jnp.asarray(r)


def _wave_stats(ts):
    """Shared latency helper (p50/p95/p99 + count) — one percentile path for
    the wave replays AND the request engine, so numbers compare across
    modes (serving.stats)."""
    from repro.serving.stats import latency_stats

    return latency_stats(ts)


def _cf_wave(state, rng, args, wave):
    """One request wave: batched pair predictions + top-N recommendations,
    each warmed once then timed per jitted call."""
    from repro.core import knn

    u = state.ratings.shape[0]
    p = state.ratings.shape[1]

    def pair_batch():
        users = jnp.asarray(rng.integers(0, u, args.batch).astype(np.int32))
        items = jnp.asarray(rng.integers(0, p, args.batch).astype(np.int32))
        return users, items

    users, items = pair_batch()
    jax.block_until_ready(  # warm: compiles for the current (U, P) shapes
        knn.predict_pairs_graph(state.graph, state.ratings, users, items))
    pair_ts = []
    for _ in range(args.requests):
        users, items = pair_batch()
        t0 = time.perf_counter()
        out = knn.predict_pairs_graph(state.graph, state.ratings, users, items)
        jax.block_until_ready(out)
        pair_ts.append(time.perf_counter() - t0)
    if not bool(jnp.isfinite(out).all()):
        raise RuntimeError("non-finite predictions in serve wave")

    topn_users = jnp.asarray(rng.integers(0, u, args.batch).astype(np.int32))
    jax.block_until_ready(knn.recommend_topn_graph(
        state.graph, state.ratings, topn_users, n=args.topn))
    topn_ts = []
    for _ in range(max(1, args.requests // 4)):
        topn_users = jnp.asarray(rng.integers(0, u, args.batch).astype(np.int32))
        t0 = time.perf_counter()
        items_r, _ = knn.recommend_topn_graph(
            state.graph, state.ratings, topn_users, n=args.topn)
        jax.block_until_ready(items_r)
        topn_ts.append(time.perf_counter() - t0)

    ps, ts = _wave_stats(pair_ts), _wave_stats(topn_ts)
    print(f"wave {wave}: U={u} predict {args.requests}x{args.batch} pairs "
          f"{ps.brief()} | top-{args.topn} x{args.batch} users {ts.brief()}")


def _serve_cf(args):
    from repro.core import LandmarkSpec, RatingMatrix, fit, fold_in
    from repro.train.checkpoint import (latest_step, load_landmark_state,
                                        save_landmark_state)

    arch = registry.get("landmark_cf")
    spec: LandmarkSpec = arch.smoke_model if args.smoke else arch.model
    if args.smoke:
        args.users, args.items = min(args.users, 512), min(args.items, 128)
        args.requests = min(args.requests, 8)
        args.foldin = min(args.foldin, 16)
        args.waves = min(args.waves, 2)

    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="cf_serve_")
    rng = np.random.default_rng(0)

    if latest_step(ckpt_dir) is None:
        r = _synth_ratings(rng, args.users, args.items)
        t0 = time.perf_counter()
        st = fit(jax.random.PRNGKey(0),
                 RatingMatrix(r, args.users, args.items), spec)
        jax.block_until_ready(st.graph.weights)
        t_fit = time.perf_counter() - t0
        save_landmark_state(ckpt_dir, st, compact=args.compact)
        print(f"fit U={args.users} P={args.items} n={spec.n_landmarks} "
              f"k={st.graph.k}: {t_fit*1e3:.0f}ms -> checkpointed {ckpt_dir}")

    t0 = time.perf_counter()
    state = load_landmark_state(ckpt_dir, widen=False)
    t_load = time.perf_counter() - t0
    stored_compact = state.graph.is_compact  # what is actually on disk
    art_kb = (state.graph.indices.nbytes + state.graph.weights.nbytes) / 1024
    if stored_compact:
        state = dataclasses.replace(state, graph=state.graph.to_full())
    print(f"loaded U={state.ratings.shape[0]} graph k={state.graph.k} "
          f"({art_kb:.0f}KB{', stored compact' if stored_compact else ''}): "
          f"{t_load*1e3:.0f}ms")

    # fold-in stream: sized from the ARTIFACT's item space, not the CLI flags
    # (reusing --ckpt with different --users/--items must still be correct)
    n_items = state.ratings.shape[1]
    fold_stream = _synth_ratings(rng, args.foldin * max(args.waves - 1, 0),
                                 n_items)
    for wave in range(args.waves):
        _cf_wave(state, rng, args, wave)
        if wave == args.waves - 1:
            break
        batch = fold_stream[wave * args.foldin:(wave + 1) * args.foldin]
        jax.block_until_ready(  # warm the fold-in executable for this shape
            fold_in(state, batch, spec, backend=args.graph_backend))
        t0 = time.perf_counter()
        state = fold_in(state, batch, spec, backend=args.graph_backend)
        jax.block_until_ready(state.graph.weights)
        dt = time.perf_counter() - t0
        print(f"fold-in +{args.foldin} users: {dt*1e3:.1f}ms "
              f"(U {state.ratings.shape[0] - args.foldin}"
              f"->{state.ratings.shape[0]}, no refit)")
    print("cf serve: done")


# -------------------------------------------------------------- cf lifecycle
IVF_RECALL_SLO = 0.95  # serving recall target; nprobe escalates to hold it


def _timed_requests(bst, rng, args):
    """One request wave against a BucketedState: warm (a cache hit except on
    bucket growth), then time per jitted call. Returns (pair_ts, topn_ts)."""
    from repro.lifecycle import buckets

    u = int(bst.n_valid)
    p = bst.n_items

    def pair_batch():
        users = jnp.asarray(rng.integers(0, u, args.batch).astype(np.int32))
        items = jnp.asarray(rng.integers(0, p, args.batch).astype(np.int32))
        return users, items

    users, items = pair_batch()
    jax.block_until_ready(buckets.predict_pairs(bst, users, items))
    jax.block_until_ready(buckets.recommend_topn(bst, users, n=args.topn))
    pair_ts, topn_ts = [], []
    for _ in range(args.requests):
        users, items = pair_batch()
        t0 = time.perf_counter()
        out = buckets.predict_pairs(bst, users, items)
        jax.block_until_ready(out)
        pair_ts.append(time.perf_counter() - t0)
    if not bool(jnp.isfinite(out).all()):
        raise RuntimeError("non-finite predictions in lifecycle wave")
    for _ in range(max(1, args.requests // 4)):
        users, _ = pair_batch()
        t0 = time.perf_counter()
        items_r, _ = buckets.recommend_topn(bst, users, n=args.topn)
        jax.block_until_ready(items_r)
        topn_ts.append(time.perf_counter() - t0)
    return pair_ts, topn_ts


def _withhold(rng, batch, frac):
    """Split an arrival block into (train, holdout triples): each rated entry
    is withheld with probability ``frac`` (zeroed in the train block)."""
    rated = batch != 0
    hold = rated & (rng.random(batch.shape) < frac)
    rows, cols = np.nonzero(hold)
    train = batch * ~hold
    return train.astype(np.float32), rows.astype(np.int32), \
        cols.astype(np.int32), batch[rows, cols].astype(np.float32)


def _clamp_lifecycle_smoke(args):
    """CI-sized limits, shared by the single-device and --mesh replays."""
    args.users, args.items = min(args.users, 256), min(args.items, 96)
    args.waves = min(args.waves, 8)
    args.arrivals = min(args.arrivals, 48)
    args.requests = min(args.requests, 8)
    args.batch = min(args.batch, 128)
    args.foldin = min(args.foldin, 32)
    args.min_bucket = min(args.min_bucket, 256)


def _offer_holdout(mon, rng, key, start_id, hrows, hcols, hvals, res_batch):
    """Offer withheld triples to the reservoir at its fixed batch shape
    (subsample when the arrival withheld more than one offer holds). User
    ids are ``start_id + row`` — logical ids on both replay paths."""
    from repro.lifecycle import monitor

    if len(hrows) > res_batch:
        pick = rng.choice(len(hrows), res_batch, replace=False)
        hrows, hcols, hvals = hrows[pick], hcols[pick], hvals[pick]
    hu = np.zeros(res_batch, np.int32)
    hi = np.zeros(res_batch, np.int32)
    hr = np.zeros(res_batch, np.float32)
    hu[:len(hrows)] = start_id + hrows
    hi[:len(hrows)] = hcols
    hr[:len(hrows)] = hvals
    return monitor.reservoir_add(mon, key, jnp.asarray(hu), jnp.asarray(hi),
                                 jnp.asarray(hr), jnp.int32(len(hrows)))


def _ivf_probe_sample(index, bst, rng, spec, args):
    """One wave's retrieval probe sample: fresh query rows + the exact
    (nprobe == n_clusters) reference. The reference is nprobe-independent,
    so the SLO escalation loop reuses it and re-searches only the cheap
    approximate side — and every escalation step is judged on the SAME
    sample (a resample per step could end the loop on a lucky draw)."""
    from repro import retrieval as rt

    u = int(bst.n_valid)
    k = bst.state.graph.k
    qids = jnp.asarray(rng.integers(0, u, min(args.batch, u)).astype(np.int32))
    qrep = bst.state.representation[qids]
    exact = rt.search(index, qrep, k, index.n_clusters, spec.d2,
                      self_ids=qids)
    return qids, qrep, k, exact


def _ivf_probe_recall(index, probe, nprobe, measure):
    """recall@k of the serving-nprobe search vs the wave's exact reference —
    the serve-path analogue of the ivf_vs_streaming bench row."""
    from repro import retrieval as rt

    qids, qrep, k, (ve, ie) = probe
    va, ia = rt.search(index, qrep, k, nprobe, measure, self_ids=qids)
    return float(rt.recall_at_k(ia, ie, va, ve))


def _serve_cf_lifecycle(args):
    """Replay a drifting stream through the fit→serve→monitor→refresh loop."""
    from repro.configs.landmark_cf import REFRESH, SMOKE_REFRESH
    from repro.core import LandmarkSpec, RatingMatrix, fit, knn
    from repro.data.synthetic import drifting_ratings
    from repro.lifecycle import buckets, monitor, policy
    from repro.lifecycle.monitor import _holdout_stats
    from repro.lifecycle.refresh import RefreshManager
    from repro.train.checkpoint import (landmark_state_meta, latest_step,
                                        load_landmark_state,
                                        save_landmark_state)

    arch = registry.get("landmark_cf")
    spec: LandmarkSpec = arch.smoke_model if args.smoke else arch.model
    # Landmark refresh only helps if reselection can *move* the landmarks to
    # the drifted population; coresets (diversity-seeking) does, popularity
    # (count-ranked, ties to the incumbents) provably does not — measured in
    # benchmarks.run refresh_vs_refit and docs/lifecycle.md.
    spec = dataclasses.replace(spec, selection=args.selection)
    rspec = SMOKE_REFRESH if args.smoke else REFRESH
    if args.compact_serving:
        rspec = dataclasses.replace(rspec, compact_serving=True)
    if args.smoke:
        _clamp_lifecycle_smoke(args)

    stream = dict(n_waves=args.waves, drift=args.drift)
    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="cf_lifecycle_")
    rng = np.random.default_rng(0)
    bq = args.foldin  # fold-in batch bucket: b is padded to this, always

    o = None
    if args.trace_dir or args.metrics_json:
        # lifecycle replay observability: per-wave drift gauges land in the
        # registry, and the installed tracer catches the background refresh
        # spans (refresh.fit / refresh.commit / refresh.ivf_rebuild)
        o = obslib.Observability(sample_rate=args.sample_rate, seed=0)
        obslib.install(o)

    # request-path executables: counted as deltas over this replay, so a warm
    # jit cache (e.g. pytest running other cases first) cannot skew the report
    families = {
        "pair": knn.predict_pairs_graph,
        "topn": knn.recommend_topn_graph,
        "fold": buckets.fold_in_bucketed,
        "holdout": _holdout_stats,
    }
    cache0 = {name: fn._cache_size() for name, fn in families.items()}

    # ---- base generation: fit on the wave-0 population, commit, bucket -----
    # a reused --ckpt dir keeps earlier runs' committed steps; namespace this
    # run's generations above them so latest_step stays this run's artifact
    prev = latest_step(ckpt_dir)
    gen0 = prev + 1 if prev is not None else 0
    r0 = drifting_ratings(0, 0, args.users, args.items, **stream)
    t0 = time.perf_counter()
    st = fit(jax.random.PRNGKey(0),
             RatingMatrix(jnp.asarray(r0), args.users, args.items), spec)
    jax.block_until_ready(st.graph.weights)
    save_landmark_state(ckpt_dir, st, step=gen0)
    base_cov = float(monitor.batch_coverage(
        st.representation, jnp.ones(args.users)))
    bst = buckets.from_state(st, args.min_bucket, args.growth)
    caps_used = {(bst.capacity, False)}  # (capacity, serving-compact?)
    mon = monitor.init_monitor(rspec.reservoir, args.users, base_cov)
    pol = policy.PolicyState(generation=gen0)

    # optional IVF retrieval sidecar: index over the landmark embedding,
    # appended on fold-in, rebuilt by the background refresh and by the
    # skew-gated proactive rebalance (docs/retrieval.md)
    use_ivf = args.retrieval == "ivf"
    index = retrieval = None
    recalls = []
    if use_ivf:
        from repro import retrieval as rt

        user_ivf = rt.IVFSpec(
            n_clusters=args.clusters or None, nprobe=args.nprobe or None)

        def resolve_serving_ivf(u):
            cfg = rt.resolve_ivf(user_ivf, u)
            if args.smoke and not args.nprobe:
                # smoke scale asks for k=13 of ~256 rows — a twentieth of
                # the population per query — so a quarter of the cells
                # cannot hold recall >= 0.95; probe half instead
                cfg = dataclasses.replace(
                    cfg, nprobe=max(cfg.nprobe, cfg.n_clusters // 2))
            return cfg

        retrieval = resolve_serving_ivf(args.users)
        index = rt.build_index(bst.state.representation, retrieval, spec.d2,
                               n_valid=bst.n_valid)
    manager = RefreshManager(ckpt_dir, spec, compact=rspec.compact_serving,
                             compact_max_rows=rspec.compact_max_rows,
                             ivf=user_ivf if use_ivf else None)
    pending = None  # (generation, snapshot rows) of the refit in flight
    last_refit = None  # same, for the committed generation (oracle check)
    swap_wave = pre_post = None
    print(f"gen {gen0}: fit U={args.users} P={args.items} n={spec.n_landmarks} "
          f"k={st.graph.k} in {(time.perf_counter()-t0)*1e3:.0f}ms, bucket "
          f"{bst.capacity} (schedule: min={args.min_bucket} x{args.growth:g}) "
          f"-> {ckpt_dir}")
    if use_ivf:
        print(f"retrieval: ivf C={index.n_clusters} cap={index.capacity} "
              f"nprobe={retrieval.nprobe} (exact at nprobe={index.n_clusters})")

    res_batch = rspec.reservoir  # fixed reservoir-offer shape: one executable
    keyseq = iter(jax.random.split(jax.random.PRNGKey(42), 2 * args.waves + 8))
    for wave in range(args.waves):
        pair_ts, topn_ts = _timed_requests(bst, rng, args)
        ps, ts_ = _wave_stats(pair_ts), _wave_stats(topn_ts)

        # ---- arrivals: withhold holdout ratings, fold the rest in ----------
        if wave + 1 < args.waves:
            arr = drifting_ratings(0, wave + 1, args.arrivals, args.items,
                                   **stream)
            train, hrows, hcols, hvals = _withhold(rng, arr, rspec.holdout_frac)
            start_id = int(bst.n_valid)  # arrival i becomes row start_id + i
            bst = buckets.fold_in_rows(bst, train, bq, spec,
                                       args.min_bucket, args.growth)
            caps_used.add((bst.capacity, bst.state.graph.is_compact))
            rep_rows = bst.state.representation[start_id:start_id + len(train)]
            mon = monitor.observe_fold_in(mon, rep_rows, jnp.int32(len(train)))
            mon = _offer_holdout(mon, rng, next(keyseq), start_id,
                                 hrows, hcols, hvals, res_batch)
            if use_ivf:  # masked append under the frozen quantizer
                index, _ = rt.ensure_index_capacity(index, len(train))
                index = rt.append(index.to_full(), rep_rows,
                                  start_id + jnp.arange(len(train)), spec.d2,
                                  spill_choices=retrieval.spill_choices)

        # ---- drift detection + refresh decision ----------------------------
        snap = monitor.holdout_snapshot(mon, bst)
        if o is not None:
            monitor.publish_snapshot(o.registry, snap)
        if math.isnan(pol.base_mae) and snap.holdout_count >= rspec.min_holdout:
            pol.base_mae = snap.mae  # post-fit baseline, first healthy holdout
        fire, reasons = policy.decide(pol, rspec, snap)
        if fire:
            gen = pol.generation + 1
            rows = bst.host_ratings(0, int(bst.n_valid))
            # request() declines while the previous refit thread is still
            # winding down; keep the streak and retry next wave instead of
            # marking a refresh that never launched
            if manager.request(rows, gen):
                policy.on_fire(pol)
                pending = (gen, rows)
                print(f"wave {wave}: gen {pol.generation} refresh -> gen {gen} "
                      f"launched in background ({'; '.join(reasons)})")

        # ---- poll the background refit; swap atomically when committed -----
        done = manager.poll()
        if done is None and wave == args.waves - 1 and manager.busy:
            manager.join()  # drain so the replay always reports the swap
            done = manager.poll()
        if done is not None:
            if use_ivf:
                gen, st_new, new_index = done  # index rebuilt inside the swap
            else:
                gen, st_new = done
            mae_pre = snap.mae  # nothing touched mon/bst since the snapshot
            snap_u = st_new.ratings.shape[0]
            cur_n = int(bst.n_valid)
            new_bst = buckets.from_state(st_new, args.min_bucket, args.growth)
            # users folded while the refit ran: fold the delta into the new gen
            delta = bst.host_ratings(snap_u, cur_n)
            bst = buckets.fold_in_rows(new_bst, delta, bq, spec,
                                       args.min_bucket, args.growth)
            caps_used.add((bst.capacity, bst.state.graph.is_compact))
            if use_ivf and len(delta):  # swap the index + append the delta
                new_index, _ = rt.ensure_index_capacity(new_index, len(delta))
                new_index = rt.append(
                    new_index, bst.state.representation[snap_u:cur_n],
                    snap_u + jnp.arange(len(delta)), spec.d2,
                    spill_choices=retrieval.spill_choices)
            if use_ivf:
                index = new_index
                # refreshed landmarks restore cell structure: drop any SLO
                # escalation back to the default probe budget
                retrieval = resolve_serving_ivf(int(bst.n_valid))
            if policy.should_compact(rspec, bst.capacity):
                # lifecycle-driven compaction: serve the uint16/bf16 graph
                # until the next fold-in/growth widens it (docs/lifecycle.md)
                bst = buckets.compact_state(bst)
                caps_used.add((bst.capacity, True))
                art_kb = (bst.state.graph.indices.nbytes
                          + bst.state.graph.weights.nbytes) / 1024
                if use_ivf:  # --compact-serving covers the index too
                    index = index.to_compact()
                    art_kb += (index.lists.nbytes + index.rows.nbytes
                               + index.centroids.nbytes) / 1024
                print(f"wave {wave}: serving graph compacted "
                      f"(uint16/bf16, {art_kb:.0f}KB resident)")
            new_cov = float(monitor.batch_coverage(
                st_new.representation, jnp.ones(snap_u)))
            mon = monitor.rebase(mon, int(bst.n_valid), new_cov)
            snap, reasons = monitor.holdout_snapshot(mon, bst), []
            if o is not None:
                monitor.publish_snapshot(o.registry, snap)
            mae_post = snap.mae
            policy.on_swap(pol, gen, mae_post, rspec)
            last_refit = pending
            pending = None
            swap_wave, pre_post = wave, (mae_pre, mae_post)
            print(f"wave {wave}: swapped in gen {gen} (U={snap_u}+{len(delta)} "
                  f"delta, serving uninterrupted) holdout MAE "
                  f"{mae_pre:.4f} -> {mae_post:.4f}")

        ivf_note = ""
        if use_ivf:
            # list-skew gate first — the same trigger plumbing as the mesh
            # shard repack: drifted arrivals pile into cells the frozen
            # quantizer does not cover, and the repack re-cells them before
            # the next wave serves
            skew = monitor.shard_skew(index.fill)
            if policy.should_rebalance(pol, rspec, skew):
                retrieval = resolve_serving_ivf(int(bst.n_valid))
                index = rt.build_index(bst.state.representation, retrieval,
                                       spec.d2, n_valid=bst.n_valid)
                print(f"wave {wave}: ivf lists rebalanced (skew {skew:.2f} > "
                      f"{rspec.max_skew:.2f}) -> C={index.n_clusters} "
                      f"cap={index.capacity}")
                skew = monitor.shard_skew(index.fill)
            # then probe retrieval health of the config the next wave serves:
            # recall@k of the serving-nprobe search vs the exact path, with
            # an SLO feedback loop — drift degrades the frozen-landmark
            # representation (neighbors diffuse across cells), so recall is
            # held by *probing more cells* until the refresh swap restores
            # the embedding and resets nprobe to the cheap default
            probe = _ivf_probe_sample(index, bst, rng, spec, args)
            rec = _ivf_probe_recall(index, probe, retrieval.nprobe, spec.d2)
            while rec < IVF_RECALL_SLO and retrieval.nprobe < index.n_clusters:
                esc = min(index.n_clusters, max(retrieval.nprobe + 1,
                                                (retrieval.nprobe * 3) // 2))
                retrieval = dataclasses.replace(retrieval, nprobe=esc)
                rec = _ivf_probe_recall(index, probe, esc, spec.d2)
                print(f"wave {wave}: ivf recall below SLO -> nprobe "
                      f"escalated to {esc}/{index.n_clusters} "
                      f"(recall {rec:.3f})")
            ee_note = ""
            if args.early_exit:
                # adaptive probing atop the escalated budget: the escalation
                # loop sets the worst-case nprobe that holds the SLO; early
                # exit then lets each query stop as soon as its own top-k
                # stops moving, so mean probed-cells/query is what serving
                # actually pays
                qids, qrep, kk, (ve, ie) = probe
                va, ia, probed = rt.search_early_exit(
                    index, qrep, kk, retrieval.nprobe, spec.d2,
                    self_ids=qids)
                ee_rec = float(rt.recall_at_k(ia, ie, va, ve))
                probed_q = float(jnp.mean(probed))
                ee_note = (f" probed/q={probed_q:.1f}/{retrieval.nprobe} "
                           f"(early-exit recall {ee_rec:.3f})")
            recalls.append(rec)
            ivf_note = (f" | ivf recall@{bst.state.graph.k}={rec:.3f} "
                        f"nprobe={retrieval.nprobe} skew={skew:.2f}"
                        + ee_note)
        print(f"wave {wave}: gen {pol.generation} U={int(bst.n_valid)}"
              f"/cap{bst.capacity} predict {args.requests}x{args.batch} pairs "
              f"{ps.brief()} | top-{args.topn} {ts_.brief()} | "
              f"mae={snap.mae:.4f} cov={snap.coverage_ratio:.2f} "
              f"fold={snap.foldin_frac:.2f}" + ivf_note
              + (f" | breach: {'; '.join(reasons)}" if reasons else ""))

    # ---- replay report: recompiles, swap latency, oracle-exactness ---------
    counts = {name: fn._cache_size() - cache0[name]
              for name, fn in families.items()}
    print(f"executables per request-path family: {counts} "
          f"(buckets used: {sorted(caps_used)})")
    worst = max(counts.values())
    assert worst <= len(caps_used), (
        f"recompile count {counts} exceeds bucket count {len(caps_used)} — "
        "the bucketed steps must compile once per bucket, not per fold-in")
    if pre_post is not None:
        mae_pre, mae_post = pre_post
        print(f"refresh: fired gen {pol.generation} at wave {swap_wave}, "
              f"holdout MAE {mae_pre:.4f} -> {mae_post:.4f}")
        assert mae_post <= mae_pre + 1e-6, (
            "refresh must not degrade holdout MAE on the drifting stream")
        # oracle: the served artifact is byte-equal to a from-scratch fit on
        # the accumulated matrix (checkpoint round-trip included)
        gen, rows = last_refit
        loaded = load_landmark_state(ckpt_dir, step=gen)
        assert latest_step(ckpt_dir) == gen, (latest_step(ckpt_dir), gen)
        oracle = fit(jax.random.PRNGKey(gen),
                     RatingMatrix(jnp.asarray(rows), *rows.shape), spec)
        og = oracle.graph
        if landmark_state_meta(ckpt_dir, gen)["compact"]:
            og = og.to_compact().to_full()  # artifact stored uint16/bf16
        exact = (np.array_equal(np.asarray(loaded.graph.indices),
                                np.asarray(og.indices))
                 and np.array_equal(np.asarray(loaded.graph.weights),
                                    np.asarray(og.weights)))
        print(f"swap oracle-exact vs from-scratch fit (gen {gen}): {exact}")
        assert exact, "swapped artifact diverged from a from-scratch fit"
    else:
        print("refresh: never fired (stream did not drift past thresholds)")
        if args.smoke:
            raise AssertionError(
                "smoke lifecycle replay must exercise a refresh; "
                "tune --drift/--waves or the smoke RefreshSpec")
    if use_ivf:
        print(f"ivf retrieval: recall@k per wave "
              f"{[f'{r:.3f}' for r in recalls]} (mean "
              f"{np.mean(recalls):.3f}, SLO {IVF_RECALL_SLO}) ending at "
              f"nprobe={retrieval.nprobe}/{index.n_clusters}")
        if args.smoke:
            assert np.mean(recalls) >= IVF_RECALL_SLO, (
                f"ivf smoke recall {np.mean(recalls):.3f} < {IVF_RECALL_SLO} "
                "on the drifting stream — the nprobe escalation + skew "
                "rebuild + refresh loop failed to hold the SLO")
    if o is not None:
        from repro.retrieval import publish_retrieval
        obslib.publish_compile_counts(o.registry, families, cache0)
        if use_ivf:
            publish_retrieval(
                o.registry, nprobe=retrieval.nprobe,
                clusters=index.n_clusters,
                recall=(float(np.mean(recalls)) if recalls
                        else float("nan")),
                early_exit=bool(args.early_exit), probes=len(recalls))
        else:
            publish_retrieval(o.registry)
        if args.trace_dir:
            tp = o.export_trace(args.trace_dir)
            print(f"obs: {len(o.tracer.events())} spans -> {tp}")
        if args.metrics_json:
            print(f"obs: metrics snapshot -> "
                  f"{o.export_metrics(args.metrics_json)}")
        obslib.uninstall()
    print("cf lifecycle: done")


# ------------------------------------------------------ cf lifecycle, sharded
def _parse_mesh(arg: str):
    """``pod=2,data=4`` -> (("pod", "data"), (2, 4))."""
    names, sizes = [], []
    for part in arg.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise ValueError(f"--mesh expects name=size pairs, got {part!r}")
        names.append(name.strip())
        sizes.append(int(size))
    return tuple(names), tuple(sizes)


def _foldin_replication_check(sst, bq, spec):
    """Prove the sharded fold-in keeps the row space sharded: no aval inside a
    shard_map body — and no non-shard_map eqn output anywhere — carries the
    full (S*C) row dimension. Returns (n_avals_scanned, offenders)."""
    from repro.core.landmark_cf import fold_in_sharded

    rows = sst.state.ratings.shape[0]
    p = sst.state.ratings.shape[1]
    bq = min(bq, sst.capacity)  # driver grows capacity before bigger batches
    fn = lambda s, nr: fold_in_sharded(s, nr, jnp.int32(1), jnp.int32(0), spec)
    jaxpr = jax.make_jaxpr(fn)(sst, jnp.zeros((bq, p), jnp.float32))

    seen, bad = [], []

    def scan(jx, inside):
        for eqn in jx.eqns:
            is_sh = eqn.primitive.name == "shard_map"
            passthrough = is_sh or eqn.primitive.name == "jit"
            if eqn.primitive.name == "sharding_constraint":
                # pinning rows onto the mesh axes keeps them sharded; a
                # constraint whose row dim is unpartitioned WOULD replicate
                spec = getattr(eqn.params.get("sharding"), "spec", None)
                passthrough = bool(spec and len(spec) and spec[0])
            for v in eqn.outvars:
                shp = getattr(v.aval, "shape", None) or ()
                seen.append(shp)
                # a shard_map/jit eqn's *result* is the sharded array itself
                # (their bodies are scanned recursively); any other eqn at
                # full row size is a materialization
                if shp and shp[0] >= rows and (inside or not passthrough):
                    bad.append((eqn.primitive.name, shp))
            for pv in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        pv, is_leaf=lambda x: hasattr(x, "jaxpr")
                        or hasattr(x, "eqns")):
                    ij = getattr(sub, "jaxpr", sub)
                    if hasattr(ij, "eqns"):
                        scan(ij, inside or is_sh)

    scan(jaxpr.jaxpr, False)

    # and the compiled executable must emit row-sharded outputs
    comp = jax.jit(fn).lower(sst, jnp.zeros((bq, p), jnp.float32)).compile()
    shs = jax.tree_util.tree_leaves(comp.output_shardings)
    row_sharded = sum(
        1 for s in shs
        if getattr(s, "spec", None) and len(s.spec) and s.spec[0] == sst.axes)
    return len(seen), bad, row_sharded


def _ivf_retrieval_materialization_check(index, qb, k, nprobe, mesh, axes,
                                         measure, local_budget):
    """Prove the sharded probe path never round-trips gathered candidates
    through HBM: no aval anywhere in the search jaxpr is a per-query
    candidate tensor of ``nprobe*cap`` rows — the (qb, nprobe*cap, n) /
    (qb, nprobe*cap) shapes a naive gather-then-GEMM scorer materializes.
    The rank-scan scorer peaks at (qb, cap, n) per probe rank and the merge
    tensors stay O(k)-wide, both strictly under the bound. Returns
    (n_avals_scanned, offenders)."""
    from repro import retrieval as rt

    n = index.rows.shape[2]
    cap = index.capacity
    s = int(np.prod([mesh.shape[a] for a in axes]))
    bound = nprobe * cap
    if bound <= max(s * k, k + cap):
        raise ValueError(  # merge widths would alias the candidate bound
            f"materialization check is vacuous at nprobe*cap={bound} "
            f"(merge widths {s * k}, {k + cap}); probe more cells")
    fn = lambda ix, q: rt.search_sharded(ix, q, k, nprobe, mesh, axes,
                                         measure, local_budget=local_budget)
    jaxpr = jax.make_jaxpr(fn)(index, jnp.zeros((qb, n), jnp.float32))

    seen, bad = [], []

    def scan(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                shp = getattr(v.aval, "shape", None) or ()
                seen.append(shp)
                if len(shp) >= 2 and shp[0] == qb and shp[1] >= bound:
                    bad.append((eqn.primitive.name, shp))
            for pv in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        pv, is_leaf=lambda x: hasattr(x, "jaxpr")
                        or hasattr(x, "eqns")):
                    ij = getattr(sub, "jaxpr", sub)
                    if hasattr(ij, "eqns"):
                        scan(ij)

    scan(jaxpr.jaxpr)
    return len(seen), bad


def _ivf_probe_sample_sharded(index, sst, sharded_ids, n_live, rng, spec,
                              args, mesh, axes):
    """Sharded analogue of :func:`_ivf_probe_sample`: fresh logical query
    ids, their representation rows gathered from the sharded layout, and the
    full-probe (exact, bit-identical to single-device) reference."""
    from repro import retrieval as rt

    k = sst.state.graph.k
    qids = rng.integers(0, n_live, min(args.batch, n_live)).astype(np.int32)
    qrep = sst.state.representation[sharded_ids(qids)]
    lq = jnp.asarray(qids)
    ve, ie, _ = rt.search_sharded(index, qrep, k, index.n_clusters, mesh,
                                  axes, spec.d2, self_ids=lq)
    return lq, qrep, k, (ve, ie)


def _ivf_probe_recall_sharded(index, probe, nprobe, measure, mesh, axes,
                              local_budget):
    """(recall@k, mean probed-cells/query) of the serving-nprobe sharded
    search vs the wave's exact reference. ``probed`` counts cells actually
    scored across the mesh — with a ``local_budget`` the router drops
    overflow cells on hot shards, and this is where that shows up."""
    from repro import retrieval as rt

    qids, qrep, k, (ve, ie) = probe
    va, ia, probed = rt.search_sharded(index, qrep, k, nprobe, mesh, axes,
                                       measure, self_ids=qids,
                                       local_budget=local_budget)
    return float(rt.recall_at_k(ia, ie, va, ve)), float(jnp.mean(probed))


def _serve_cf_lifecycle_sharded(args):
    """The lifecycle replay on a mesh: fit_distributed → ShardedLandmarkState
    serving → shard-local-append fold-in → monitor → distributed refresh →
    swap, with a single-device shadow replay (same landmarks, same PRNG, same
    arrival stream) asserting bit-identical predictions every wave."""
    from repro.configs.landmark_cf import REFRESH, SMOKE_REFRESH
    from repro.core import LandmarkSpec, RatingMatrix, fit, knn
    from repro.core.landmark_cf import fit_distributed, fold_in_sharded
    from repro.data.synthetic import drifting_ratings
    from repro.lifecycle import buckets, monitor, policy
    from repro.lifecycle.monitor import _holdout_stats
    from repro.lifecycle.refresh import RefreshManager
    from repro.train.checkpoint import (landmark_state_meta, latest_step,
                                        load_landmark_state,
                                        save_landmark_state)

    names, sizes = _parse_mesh(args.mesh)
    need = int(np.prod(sizes))
    if jax.device_count() < need:
        raise SystemExit(
            f"--mesh {args.mesh} needs {need} devices but jax sees "
            f"{jax.device_count()}; on CPU launch a fresh process (the "
            f"XLA_FLAGS host-platform override must precede jax init)")
    mesh = make_mesh(sizes, names)
    axes = names
    n_shards = need

    arch = registry.get("landmark_cf")
    spec: LandmarkSpec = arch.smoke_model if args.smoke else arch.model
    spec = dataclasses.replace(spec, selection=args.selection)
    rspec = SMOKE_REFRESH if args.smoke else REFRESH
    if args.compact_serving:
        print("--compact-serving is a single-device serving policy; "
              "ignored under --mesh (the sharded artifact stays f32/int32)")
    if args.smoke:
        _clamp_lifecycle_smoke(args)
    min_shard_bucket = max(8, args.min_bucket // n_shards)

    stream = dict(n_waves=args.waves, drift=args.drift)
    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="cf_sharded_")
    rng = np.random.default_rng(0)
    bq = args.foldin

    o = None
    if args.trace_dir or args.metrics_json:
        o = obslib.Observability(sample_rate=args.sample_rate, seed=0)
        obslib.install(o)

    families = {
        "pair": knn.predict_pairs_graph,
        "topn": knn.recommend_topn_graph,
        "fold": fold_in_sharded,
        "holdout": _holdout_stats,
    }
    cache0 = {name: fn._cache_size() for name, fn in families.items()}

    # ---- base generation: fit_distributed + single-device shadow oracle ----
    prev = latest_step(ckpt_dir)
    gen0 = prev + 1 if prev is not None else 0
    r0 = drifting_ratings(0, 0, args.users, args.items, **stream)
    t0 = time.perf_counter()
    st = fit_distributed(jax.random.PRNGKey(0), jnp.asarray(r0), spec, mesh,
                         user_axes=axes)
    jax.block_until_ready(st.graph.weights)
    t_fit = time.perf_counter() - t0
    save_landmark_state(ckpt_dir, st, step=gen0)
    shadow_st = fit(jax.random.PRNGKey(0),
                    RatingMatrix(jnp.asarray(r0), args.users, args.items), spec)
    sst = buckets.from_state_sharded(st, mesh, axes, min_shard_bucket,
                                     args.growth)
    bst = buckets.from_state(shadow_st, args.min_bucket, args.growth)
    # logical row id -> (shard, slot); slots survive capacity regrowth
    u_per = -(-args.users // n_shards)
    id_shard = (np.arange(args.users) // u_per).astype(np.int32)
    id_slot = (np.arange(args.users) % u_per).astype(np.int32)
    meta0 = landmark_state_meta(ckpt_dir, gen0)
    print(f"gen {gen0}: fit_distributed U={args.users} over "
          f"{'x'.join(f'{a}={s}' for a, s in zip(axes, sizes))} "
          f"(S={n_shards}, u/shard={u_per}) n={spec.n_landmarks} "
          f"k={st.graph.k} in {t_fit*1e3:.0f}ms; per-shard bucket "
          f"C={sst.capacity} (min={min_shard_bucket} x{args.growth:g}); "
          f"checkpoint row shards: {meta0['row_shards']} -> {ckpt_dir}")

    # ---- one-time proof: fold-in never materializes replicated (U, n) ------
    n_avals, offenders, row_sharded = _foldin_replication_check(sst, bq, spec)
    print(f"fold-in sharding check: {n_avals} avals scanned, "
          f"{len(offenders)} full-row materializations, "
          f"{row_sharded} row-sharded outputs")
    assert not offenders, offenders
    assert row_sharded >= 4, "rep/ratings/graph outputs must stay row-sharded"

    def sharded_ids(logical):
        return jnp.asarray(id_shard[logical] * sst.capacity
                           + id_slot[logical])

    def id_map_arr():
        m = np.zeros(n_shards * sst.capacity, np.int32)
        n = len(id_shard)
        m[:n] = id_shard * sst.capacity + id_slot
        return jnp.asarray(m)

    # optional sharded-IVF retrieval sidecar: posting lists block-partitioned
    # over the mesh cells, probes routed shard-local, results merged from
    # (b, k) lists only (repro.retrieval.sharded; docs/retrieval.md). Lists
    # store LOGICAL row ids — the reservoir's id space — so recall probes
    # need no translation.
    use_ivf = args.retrieval == "ivf"
    index = retrieval = user_ivf = None
    recalls = []
    if use_ivf:
        from repro import retrieval as rt

        user_ivf = rt.IVFSpec(
            n_clusters=args.clusters or None, nprobe=args.nprobe or None)

        def resolve_serving_ivf(u):
            cfg = rt.resolve_ivf_sharded(user_ivf, u, n_shards)
            if args.smoke and not args.nprobe:
                # same smoke-scale bump as the single-device replay: k is a
                # big fraction of U, a quarter of the cells can't hold recall
                cfg = dataclasses.replace(
                    cfg, nprobe=max(cfg.nprobe, cfg.n_clusters // 2))
            return cfg

        def probe_budget(nprobe):
            # bound per-shard tail work to ~2x the even split; at full probe
            # search_sharded pins the budget to C/S regardless
            return min(nprobe, max(1, 2 * (-(-nprobe // n_shards))))

        retrieval = resolve_serving_ivf(args.users)
        # build on the logical-order representation (fit output), place on
        # the mesh — bitwise the same index a single device would build
        index = rt.build_index_sharded(st.representation, retrieval, mesh,
                                       axes, spec.d2)
        print(f"retrieval: sharded ivf C={index.n_clusters} "
              f"({index.n_clusters // n_shards} cells/shard) "
              f"cap={index.capacity} nprobe={retrieval.nprobe} "
              f"budget={probe_budget(retrieval.nprobe)}/shard")
        # one-time proof: the probe path never materializes the gathered
        # (qb, nprobe*cap, n) candidate tensor a naive scorer would build
        ck_np = max(2, retrieval.nprobe)
        n_avals, offenders = _ivf_retrieval_materialization_check(
            index, args.batch, st.graph.k, ck_np, mesh, axes, spec.d2,
            probe_budget(ck_np))
        print(f"ivf serve-path check: {n_avals} avals scanned, "
              f"{len(offenders)} candidate-tensor materializations")
        assert not offenders, offenders

    base_cov = float(monitor.batch_coverage(
        shadow_st.representation, jnp.ones(args.users)))
    mon = monitor.init_monitor(rspec.reservoir, args.users, base_cov)
    pol = policy.PolicyState(generation=gen0)
    manager = RefreshManager(ckpt_dir, spec, mesh=mesh, row_axes=axes,
                             ivf=user_ivf if use_ivf else None)
    pending = None
    swap_wave = pre_post = None
    identical_waves = 0
    caps_sh, caps_lo = {sst.capacity}, {bst.capacity}
    res_batch = rspec.reservoir
    keyseq = iter(jax.random.split(jax.random.PRNGKey(42), 2 * args.waves + 8))

    for wave in range(args.waves):
        # ---- bit-identity probe vs the single-device shadow ----------------
        prng = np.random.default_rng(10_000 + wave)
        n_live = len(id_shard)
        pu = prng.integers(0, n_live, args.batch).astype(np.int32)
        pi = jnp.asarray(prng.integers(0, args.items,
                                       args.batch).astype(np.int32))
        p_sh = np.asarray(buckets.predict_pairs_sharded(
            sst, sharded_ids(pu), pi))
        p_lo = np.asarray(buckets.predict_pairs(bst, jnp.asarray(pu), pi))
        t_sh, s_sh = buckets.recommend_topn_sharded(
            sst, sharded_ids(pu), n=args.topn)
        t_lo, s_lo = buckets.recommend_topn(bst, jnp.asarray(pu),
                                            n=args.topn)
        same = (np.array_equal(p_sh, p_lo)
                and np.array_equal(np.asarray(t_sh), np.asarray(t_lo))
                and np.array_equal(np.asarray(s_sh), np.asarray(s_lo)))
        identical_waves += bool(same)
        assert same, (
            f"wave {wave}: sharded predictions diverged from the "
            f"single-device shadow (max |Δ|={np.abs(p_sh - p_lo).max()})")

        # ---- timed requests on the sharded path (probe above was the warm) -
        pair_ts, topn_ts = [], []
        for _ in range(args.requests):
            qu = sharded_ids(rng.integers(0, n_live,
                                          args.batch).astype(np.int32))
            qi = jnp.asarray(rng.integers(0, args.items,
                                          args.batch).astype(np.int32))
            t0 = time.perf_counter()
            out = buckets.predict_pairs_sharded(sst, qu, qi)
            jax.block_until_ready(out)
            pair_ts.append(time.perf_counter() - t0)
        if not bool(jnp.isfinite(out).all()):
            raise RuntimeError("non-finite predictions in sharded wave")
        for _ in range(max(1, args.requests // 4)):
            qu = sharded_ids(rng.integers(0, n_live,
                                          args.batch).astype(np.int32))
            t0 = time.perf_counter()
            items_r, _ = buckets.recommend_topn_sharded(sst, qu, n=args.topn)
            jax.block_until_ready(items_r)
            topn_ts.append(time.perf_counter() - t0)
        ps, ts_ = _wave_stats(pair_ts), _wave_stats(topn_ts)

        # ---- arrivals: fold into BOTH states, reservoir keeps logical ids --
        if wave + 1 < args.waves:
            arr = drifting_ratings(0, wave + 1, args.arrivals, args.items,
                                   **stream)
            train, hrows, hcols, hvals = _withhold(rng, arr,
                                                   rspec.holdout_frac)
            start_logical = n_live
            sst, fsh, fsl = buckets.fold_in_rows_sharded(
                sst, train, bq, spec, min_shard_bucket, args.growth)
            caps_sh.add(sst.capacity)
            id_shard = np.concatenate([id_shard, fsh])
            id_slot = np.concatenate([id_slot, fsl])
            bst = buckets.fold_in_rows(bst, train, bq, spec,
                                       args.min_bucket, args.growth)
            caps_lo.add(bst.capacity)
            rep_rows = sst.state.representation[
                jnp.asarray(fsh * sst.capacity + fsl)]
            mon = monitor.observe_fold_in(mon, rep_rows, jnp.int32(len(train)))
            mon = _offer_holdout(mon, rng, next(keyseq), start_logical,
                                 hrows, hcols, hvals, res_batch)
            if use_ivf:
                # plan replicated, scatter shard-local (append_sharded) —
                # bit-equal to the single-device append on gathered arrays
                index, _ = rt.ensure_index_capacity_sharded(
                    index, len(train), mesh, axes)
                index = rt.append_sharded(
                    index, rep_rows,
                    start_logical + jnp.arange(len(train)), mesh, axes,
                    spec.d2, spill_choices=retrieval.spill_choices)

        # ---- drift detection + distributed refresh -------------------------
        snap = monitor.holdout_snapshot_sharded(mon, sst, id_map_arr())
        if o is not None:
            monitor.publish_snapshot(o.registry, snap)
        if math.isnan(pol.base_mae) and snap.holdout_count >= rspec.min_holdout:
            pol.base_mae = snap.mae
        fire, reasons = policy.decide(pol, rspec, snap)
        if fire:
            gen = pol.generation + 1
            ids = id_shard.astype(np.int64) * sst.capacity + id_slot
            rows = np.asarray(sst.state.ratings)[ids]  # logical row order
            if manager.request(rows, gen):
                policy.on_fire(pol)
                pending = (gen, rows)
                print(f"wave {wave}: gen {pol.generation} refresh -> gen {gen}"
                      f" launched on the mesh ({'; '.join(reasons)})")

        # ---- poll; swap BOTH replicas when the refit commits ---------------
        done = manager.poll()
        if done is None and wave == args.waves - 1 and manager.busy:
            manager.join()
            done = manager.poll()
        if done is not None:
            if use_ivf:
                gen, st_new, new_index = done  # mesh-placed, rebuilt in swap
            else:
                gen, st_new = done
            mae_pre = snap.mae
            snap_u = st_new.ratings.shape[0]
            cur_n = len(id_shard)
            old_ids = id_shard.astype(np.int64) * sst.capacity + id_slot
            delta = np.asarray(sst.state.ratings)[old_ids[snap_u:cur_n]]
            # oracle: committed sharded artifact == single-device fit
            gen_p, rows_p = pending
            assert gen_p == gen
            oracle = fit(jax.random.PRNGKey(gen),
                         RatingMatrix(jnp.asarray(rows_p), *rows_p.shape),
                         spec)
            loaded = load_landmark_state(ckpt_dir, step=gen)
            exact = (np.array_equal(np.asarray(loaded.graph.indices),
                                    np.asarray(oracle.graph.indices))
                     and np.array_equal(np.asarray(loaded.graph.weights),
                                        np.asarray(oracle.graph.weights)))
            assert exact, ("distributed refresh artifact diverged from the "
                           "single-device from-scratch fit")
            # swap the sharded replica + rebuild the logical id map
            sst = buckets.from_state_sharded(st_new, mesh, axes,
                                             min_shard_bucket, args.growth)
            u_per = -(-snap_u // n_shards)
            id_shard = (np.arange(snap_u) // u_per).astype(np.int32)
            id_slot = (np.arange(snap_u) % u_per).astype(np.int32)
            sst, fsh, fsl = buckets.fold_in_rows_sharded(
                sst, delta, bq, spec, min_shard_bucket, args.growth)
            caps_sh.add(sst.capacity)
            id_shard = np.concatenate([id_shard, fsh])
            id_slot = np.concatenate([id_slot, fsl])
            if use_ivf:
                # swap the index with its refreshed quantizer + append the
                # rows folded while the refit ran, then drop any nprobe
                # escalation back to the default budget
                if len(delta):
                    new_index, _ = rt.ensure_index_capacity_sharded(
                        new_index, len(delta), mesh, axes)
                    drep = sst.state.representation[
                        jnp.asarray(fsh * sst.capacity + fsl)]
                    new_index = rt.append_sharded(
                        new_index, drep, snap_u + jnp.arange(len(delta)),
                        mesh, axes, spec.d2,
                        spill_choices=retrieval.spill_choices)
                index = new_index
                retrieval = resolve_serving_ivf(len(id_shard))
            # swap the shadow replica through ITS single-device fit
            bst = buckets.from_state(oracle, args.min_bucket, args.growth)
            bst = buckets.fold_in_rows(bst, delta, bq, spec,
                                       args.min_bucket, args.growth)
            caps_lo.add(bst.capacity)
            new_cov = float(monitor.batch_coverage(
                st_new.representation, jnp.ones(snap_u)))
            mon = monitor.rebase(mon, len(id_shard), new_cov)
            snap, reasons = monitor.holdout_snapshot_sharded(
                mon, sst, id_map_arr()), []
            mae_post = snap.mae
            policy.on_swap(pol, gen, mae_post, rspec)
            pending = None
            swap_wave, pre_post = wave, (mae_pre, mae_post)
            print(f"wave {wave}: swapped in gen {gen} on all {n_shards} "
                  f"shards (U={snap_u}+{len(delta)} delta, oracle-exact, "
                  f"serving uninterrupted) holdout MAE "
                  f"{mae_pre:.4f} -> {mae_post:.4f}")

        ivf_note = ""
        if use_ivf:
            # cell-skew gate: drifted arrivals pile into cells the frozen
            # quantizer doesn't cover; a breach re-cells the population in
            # logical row order (bitwise the same rebuild on any mesh)
            cskew = monitor.shard_skew(index.fill)
            if policy.should_rebalance(pol, rspec, cskew):
                retrieval = resolve_serving_ivf(len(id_shard))
                rep_log = sst.state.representation[
                    sharded_ids(np.arange(len(id_shard)))]
                index = rt.build_index_sharded(rep_log, retrieval, mesh,
                                               axes, spec.d2)
                print(f"wave {wave}: ivf lists rebalanced (cell skew "
                      f"{cskew:.2f} > {rspec.max_skew:.2f}) -> "
                      f"C={index.n_clusters} cap={index.capacity}")
                cskew = monitor.shard_skew(index.fill)
            # probe retrieval health of the config the next wave serves —
            # same SLO feedback loop as the single-device replay, but the
            # probes route through the sharded posting lists and `probed`
            # counts cells actually scored across the mesh
            probe = _ivf_probe_sample_sharded(index, sst, sharded_ids,
                                              len(id_shard), rng, spec,
                                              args, mesh, axes)
            rec, probed_q = _ivf_probe_recall_sharded(
                index, probe, retrieval.nprobe, spec.d2, mesh, axes,
                probe_budget(retrieval.nprobe))
            while (rec < IVF_RECALL_SLO
                   and retrieval.nprobe < index.n_clusters):
                esc = min(index.n_clusters, max(retrieval.nprobe + 1,
                                                (retrieval.nprobe * 3) // 2))
                retrieval = dataclasses.replace(retrieval, nprobe=esc)
                rec, probed_q = _ivf_probe_recall_sharded(
                    index, probe, esc, spec.d2, mesh, axes,
                    probe_budget(esc))
                print(f"wave {wave}: ivf recall below SLO -> nprobe "
                      f"escalated to {esc}/{index.n_clusters} "
                      f"(recall {rec:.3f}, probed/q={probed_q:.1f})")
            ee_note = ""
            if args.early_exit:
                # adaptive probing through the SAME router: per-shard
                # local-first budget slice, then each query retires a shard's
                # scan once its local top-k stabilizes — probed/q is cells
                # actually scored across the mesh (satellite of the engine
                # PR: the sharded path now has the single-device --early-exit
                # treatment, parity-tested at full probe)
                qids_p, qrep_p, kk, (ve, ie) = probe
                va, ia, probed = rt.search_early_exit_sharded(
                    index, qrep_p, kk, retrieval.nprobe, mesh, axes,
                    spec.d2, self_ids=qids_p,
                    local_budget=probe_budget(retrieval.nprobe))
                ee_rec = float(rt.recall_at_k(ia, ie, va, ve))
                ee_probed = float(jnp.mean(probed))
                ee_note = (f" probed/q={ee_probed:.1f}/{retrieval.nprobe} "
                           f"(early-exit recall {ee_rec:.3f})")
            recalls.append(rec)
            ivf_note = (f" | ivf recall@{sst.state.graph.k}={rec:.3f} "
                        f"nprobe={retrieval.nprobe} probed/q={probed_q:.1f} "
                        f"cellskew={cskew:.2f}" + ee_note)

        fills = np.asarray(sst.n_valid)
        # the proactive-rebalance gate rides the sharded snapshot's skew
        # signal; least-loaded placement keeps it quiet in steady state, so
        # a fire here marks the early-repack point (ROADMAP follow-up)
        rebal = policy.should_rebalance(pol, rspec, snap.shard_skew)
        print(f"wave {wave}: gen {pol.generation} U={len(id_shard)} "
              f"shards[{fills.min()}..{fills.max()}]/cap{sst.capacity} "
              f"predict {args.requests}x{args.batch} pairs {ps.brief()} | "
              f"top-{args.topn} {ts_.brief()} | mae={snap.mae:.4f} "
              f"cov={snap.coverage_ratio:.2f} fold={snap.foldin_frac:.2f} "
              f"skew={snap.shard_skew:.2f} | bit-identical: {bool(same)}"
              + ivf_note
              + (" | shard skew breach: repack at next swap" if rebal else "")
              + (f" | breach: {'; '.join(reasons)}" if reasons else ""))

    # ---- replay report -----------------------------------------------------
    counts = {name: fn._cache_size() - cache0[name]
              for name, fn in families.items()}
    budget = len(caps_sh) + len(caps_lo)  # sharded + shadow executables
    print(f"executables per request-path family: {counts} (per-shard buckets:"
          f" {sorted(caps_sh)}, shadow buckets: {sorted(caps_lo)})")
    assert max(counts.values()) <= budget, (
        f"recompile count {counts} exceeds bucket budget {budget} — the "
        "sharded steps must compile once per (capacity, batch) like the "
        "single-device path")
    print(f"predictions bit-identical to the single-device run: "
          f"{identical_waves}/{args.waves} waves")
    assert identical_waves == args.waves
    if pre_post is not None:
        mae_pre, mae_post = pre_post
        print(f"refresh: fired gen {pol.generation} at wave {swap_wave}, "
              f"holdout MAE {mae_pre:.4f} -> {mae_post:.4f}")
        assert mae_post <= mae_pre + 1e-6, (
            "refresh must not degrade holdout MAE on the drifting stream")
    else:
        print("refresh: never fired (stream did not drift past thresholds)")
        if args.smoke:
            raise AssertionError(
                "sharded smoke replay must exercise a distributed refresh; "
                "tune --drift/--waves or the smoke RefreshSpec")
    if use_ivf:
        print(f"ivf retrieval (sharded): recall@k per wave "
              f"{[f'{r:.3f}' for r in recalls]} (mean "
              f"{np.mean(recalls):.3f}, SLO {IVF_RECALL_SLO}) ending at "
              f"nprobe={retrieval.nprobe}/{index.n_clusters}")
        if args.smoke:
            assert np.mean(recalls) >= IVF_RECALL_SLO, (
                f"sharded ivf smoke recall {np.mean(recalls):.3f} < "
                f"{IVF_RECALL_SLO} — the probe router + escalation + "
                "refresh rebuild failed to hold the SLO on the mesh")
    if o is not None:
        from repro.retrieval import publish_retrieval
        obslib.publish_compile_counts(o.registry, families, cache0)
        if use_ivf:
            publish_retrieval(
                o.registry, nprobe=retrieval.nprobe,
                clusters=index.n_clusters,
                recall=(float(np.mean(recalls)) if recalls
                        else float("nan")),
                early_exit=bool(args.early_exit), probes=len(recalls))
        else:
            publish_retrieval(o.registry)
        if args.trace_dir:
            tp = o.export_trace(args.trace_dir)
            print(f"obs: {len(o.tracer.events())} spans -> {tp}")
        if args.metrics_json:
            print(f"obs: metrics snapshot -> "
                  f"{o.export_metrics(args.metrics_json)}")
        obslib.uninstall()
    print("cf sharded lifecycle: done")


# -------------------------------------------------------------- cf engine
def _serve_cf_engine(args):
    """Open-loop serving through the request engine (docs/serving.md):
    continuous micro-batching over the warm bucketed executables, bounded
    admission with load shedding, an async fold-in lane, and — under
    ``--mesh`` — the shard_map query router instead of the GSPMD gather.
    A load generator drives mixed pair/top-N/fold traffic at ``--rate``
    requests/s for ``--duration`` seconds; the run reports sustained QPS,
    p50/p95/p99 and shed rate, and ``--smoke`` asserts the SLOs under load:
    QPS > 0, zero non-finite predictions, bitwise-vs-solo verification,
    recall >= 0.95 (with ``--retrieval ivf``), and the bounded-compile and
    no-materialization guarantees. ``--mutations`` additionally opens the
    write path (docs/mutation.md): update/remove traffic on the write lane,
    an engine-fed drift monitor, and a policy-fired compacting refresh."""
    from repro.core import LandmarkSpec, RatingMatrix, fit, knn
    from repro.lifecycle import buckets
    from repro.serving import (EngineConfig, LocalBackend,
                               MutableLocalBackend, MutableShardedBackend,
                               RequestEngine, ShardedBackend)
    from repro.serving import router as srouter
    from repro.serving.stats import latency_stats

    arch = registry.get("landmark_cf")
    spec: LandmarkSpec = arch.smoke_model if args.smoke else arch.model
    spec = dataclasses.replace(spec, selection=args.selection)
    if args.smoke:
        _clamp_lifecycle_smoke(args)
        args.duration = min(args.duration, 4.0)
    rng = np.random.default_rng(0)
    n0 = args.users  # load targets the base population: valid in every gen
    mutations = bool(args.mutations)
    if mutations:
        from repro.configs.landmark_cf import REFRESH, SMOKE_REFRESH
        from repro.core.similarity import masked_similarity
        from repro.data.synthetic import mutation_events
        from repro.lifecycle import monitor, policy
        rspec = SMOKE_REFRESH if args.smoke else REFRESH
        if args.smoke:
            # a CI-length window deletes only a few percent of the base
            # population; drop the compaction gate so the smoke still
            # exercises the policy-fired refresh + tombstone compaction
            rspec = dataclasses.replace(rspec, max_tombstone_frac=0.01)

    r0 = _synth_ratings(rng, args.users, args.items)
    t0 = time.perf_counter()
    st = fit(jax.random.PRNGKey(0),
             RatingMatrix(r0, args.users, args.items), spec)
    jax.block_until_ready(st.graph.weights)
    print(f"fit U={args.users} P={args.items} n={spec.n_landmarks} "
          f"k={st.graph.k}: {(time.perf_counter()-t0)*1e3:.0f}ms")

    # on a mesh, fold launches are serialized with reads (single-process
    # host-mesh collective safety — see RequestEngine.exec_lock), so reads
    # arriving mid-fold wait out the fold; the SLO reflects that
    cfg = EngineConfig(max_batch=args.batch,
                       min_shape=min(32, args.batch),
                       queue_cap=args.batch * 8,
                       max_wait_ms=2.0,
                       slo_ms=2000.0 if args.mesh else 250.0,
                       fold_bq=args.foldin,
                       topn=args.topn)

    sharded = bool(args.mesh)
    if sharded:
        names, sizes = _parse_mesh(args.mesh)
        need = int(np.prod(sizes))
        if jax.device_count() < need:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices but jax sees "
                f"{jax.device_count()}")
        mesh = make_mesh(sizes, names)
        axes = names
        n_shards = need
        min_shard_bucket = max(8, args.min_bucket // n_shards)
        sst = buckets.from_state_sharded(st, mesh, axes, min_shard_bucket,
                                         args.growth)
        u_per = -(-args.users // n_shards)
        id_shard = (np.arange(args.users) // u_per).astype(np.int32)
        id_slot = (np.arange(args.users) % u_per).astype(np.int32)
        backend_cls = MutableShardedBackend if mutations else ShardedBackend
        backend = backend_cls(sst, id_shard, id_slot, spec,
                              min_bucket=min_shard_bucket,
                              growth=args.growth,
                              warm_shapes=cfg.batch_shapes(),
                              warm_topn=args.topn)
        # one-time jaxpr proof: the routed request path materializes no
        # replicated (S*C, .) row-space array and no (b, U) score tensor
        n_avals, offenders = srouter.materialization_check(
            sst, cfg.max_batch, args.topn)
        print(f"router materialization check: {n_avals} avals scanned, "
              f"{len(offenders)} offenders")
        assert not offenders, offenders
        # full-batch bitwise: routed == the single-device reference. In
        # --mutations mode the reference is the single-device *mutable*
        # read path: the routed side threads the (all-false) tombstone
        # operand, which re-fuses the pair reduction — its bitwise peer is
        # the solo path with the same operand, not the tomb-less one.
        shadow = buckets.from_state(st, args.min_bucket, args.growth)
        pu = rng.integers(0, n0, cfg.max_batch)
        pi = rng.integers(0, args.items, cfg.max_batch)
        routed = np.asarray(backend.predict_pairs(backend.snapshot(), pu, pi))
        ri, rs = backend.recommend_topn(backend.snapshot(), pu, args.topn)
        if mutations:
            from repro import mutation as _mut
            sh_m = _mut.from_bucketed(shadow)
            ref = np.asarray(_mut.predict_pairs(
                sh_m, jnp.asarray(pu, jnp.int32), jnp.asarray(pi, jnp.int32)))
            fi, fs = _mut.recommend_topn(sh_m, jnp.asarray(pu, jnp.int32),
                                         n=args.topn)
        else:
            ref = np.asarray(buckets.predict_pairs(
                shadow, jnp.asarray(pu, jnp.int32),
                jnp.asarray(pi, jnp.int32)))
            fi, fs = buckets.recommend_topn(shadow, jnp.asarray(pu, jnp.int32),
                                            n=args.topn)
        same = (np.array_equal(routed, ref)
                and np.array_equal(np.asarray(ri), np.asarray(fi))
                and np.array_equal(np.asarray(rs), np.asarray(fs)))
        print(f"routed vs single-device reference ({cfg.max_batch} queries): "
              f"bit-identical={same}")
        assert same, "shard_map router diverged from the reference"
        families = {"pair": srouter.predict_pairs_routed,
                    "topn": srouter._recommend_topn_routed}
    else:
        bst = buckets.from_state(st, args.min_bucket, args.growth)
        backend_cls = MutableLocalBackend if mutations else LocalBackend
        backend = backend_cls(bst, spec, min_bucket=args.min_bucket,
                              growth=args.growth,
                              warm_shapes=cfg.batch_shapes(),
                              warm_topn=args.topn)
        families = {"pair": knn.predict_pairs_graph,
                    "topn": knn.recommend_topn_graph}
    cache0 = {name: fn._cache_size() for name, fn in families.items()}

    # optional IVF sidecar: retrieval health probed *while the engine is
    # under load* (index maintenance itself rides the lifecycle loop)
    use_ivf = args.retrieval == "ivf"
    recalls, probeds, ee_recalls = [], [], []
    if use_ivf:
        from repro import retrieval as rt

        user_ivf = rt.IVFSpec(
            n_clusters=args.clusters or None, nprobe=args.nprobe or None)
        retrieval = (rt.resolve_ivf_sharded(user_ivf, n0, n_shards)
                     if sharded else rt.resolve_ivf(user_ivf, n0))
        if args.smoke and not args.nprobe:
            # same smoke-scale bump as the lifecycle replays
            retrieval = dataclasses.replace(
                retrieval,
                nprobe=max(retrieval.nprobe, retrieval.n_clusters // 2))
        index = (rt.build_index_sharded(st.representation, retrieval, mesh,
                                        axes, spec.d2) if sharded
                 else rt.build_index(st.representation, retrieval, spec.d2))
        kk = st.graph.k
        qids0 = jnp.asarray(rng.integers(0, n0, min(args.batch, n0))
                            .astype(np.int32))
        qrep0 = st.representation[qids0]
        if sharded:
            ve, ie, _ = rt.search_sharded(index, qrep0, kk, index.n_clusters,
                                          mesh, axes, spec.d2, self_ids=qids0)
        else:
            ve, ie = rt.search(index, qrep0, kk, index.n_clusters, spec.d2,
                               self_ids=qids0)

        def recall_probe():
            """(SLO recall, mean probed/q, early-exit recall or None).

            The SLO is judged on the full-budget search — the lever the
            escalation loop actually controls. Early exit rides atop the
            escalated budget as adaptive probing: its recall and probed/q
            are reported, not gated (patience exits cap probing no matter
            how far nprobe escalates, same split the lifecycle waves use).
            """
            np_ = retrieval.nprobe
            if sharded:
                lb = min(np_, max(1, 2 * (-(-np_ // n_shards))))
                va, ia, probed = rt.search_sharded(
                    index, qrep0, kk, np_, mesh, axes, spec.d2,
                    self_ids=qids0, local_budget=lb)
            else:
                va, ia = rt.search(index, qrep0, kk, np_, spec.d2,
                                   self_ids=qids0)
                probed = jnp.full((len(qids0),), np_)
            rec = float(rt.recall_at_k(ia, ie, va, ve))
            ee = None
            if args.early_exit:
                if sharded:
                    ev, ei, probed = rt.search_early_exit_sharded(
                        index, qrep0, kk, np_, mesh, axes, spec.d2,
                        self_ids=qids0, local_budget=lb)
                else:
                    ev, ei, probed = rt.search_early_exit(
                        index, qrep0, kk, np_, spec.d2, self_ids=qids0)
                ee = float(rt.recall_at_k(ei, ie, ev, ve))
            return rec, float(jnp.mean(probed)), ee

        esc_count = 0
        rec0, _pq, _ee = recall_probe()  # warm the probe executables
        while rec0 < IVF_RECALL_SLO and retrieval.nprobe < index.n_clusters:
            esc = min(index.n_clusters, max(retrieval.nprobe + 1,
                                            (retrieval.nprobe * 3) // 2))
            retrieval = dataclasses.replace(retrieval, nprobe=esc)
            esc_count += 1
            rec0, _pq, _ee = recall_probe()
        print(f"retrieval: {'sharded ' if sharded else ''}ivf "
              f"C={index.n_clusters} nprobe={retrieval.nprobe} "
              f"pre-load recall@{kk}={rec0:.3f}")

    o = None
    if args.trace_dir or args.metrics_json or args.jax_profile:
        o = obslib.Observability(sample_rate=args.sample_rate, seed=0)
        obslib.install(o)
    if o is not None and not mutations:
        # obs-mode lifecycle feed (docs/observability.md): withhold the
        # same holdout slice from each fold batch the --mutations monitor
        # would, so the exported lifecycle series carries a real holdout
        # MAE even when the write path is closed
        from repro.configs.landmark_cf import REFRESH, SMOKE_REFRESH
        from repro.lifecycle import monitor
        obs_rspec = SMOKE_REFRESH if args.smoke else REFRESH
        obs_cov = float(monitor.batch_coverage(
            st.representation, jnp.ones((n0,), jnp.float32)))
        obs_mon = monitor.init_monitor(obs_rspec.reservoir, n0, obs_cov)
        obs_keys = iter(jax.random.split(jax.random.PRNGKey(17), 64))
        # pre-warm the reservoir executable outside the timed window (the
        # feed runs on the load-loop thread, same as the --mutations path)
        jax.block_until_ready(_offer_holdout(
            obs_mon, rng, next(obs_keys), 0, np.zeros(0, np.int32),
            np.zeros(0, np.int32), np.zeros(0, np.float32),
            obs_rspec.reservoir).res_users)

    if mutations:
        # engine-mode drift monitor (docs/mutation.md): the reservoir, the
        # fold-in volume, and the tombstone fraction all accumulate from
        # LIVE engine traffic in the load loop below; the policy verdict is
        # evaluated once the window drains (writes are async — a mid-window
        # refresh would renumber rows under queued folds)
        base_cov = float(monitor.batch_coverage(
            st.representation, jnp.ones((n0,), jnp.float32)))
        mon = monitor.init_monitor(rspec.reservoir, n0, base_cov)
        pol = policy.PolicyState(generation=backend.generation)
        mkeys = iter(jax.random.split(jax.random.PRNGKey(11), 512))
        alive = np.ones(n0, bool)  # host view of not-yet-deleted base users
        removed_ids: list = []
        # pre-warm the monitor-feed executables outside the timed window:
        # the feed runs on the load-loop thread, and a ~2s in-window compile
        # would starve every cadence behind it (folds, mutation waves)
        warm_rep = masked_similarity(
            jnp.zeros((args.foldin, args.items), jnp.float32),
            backend._pub[0].landmarks, spec.d1)
        jax.block_until_ready(
            monitor.observe_fold_in(mon, warm_rep, jnp.int32(0)).coverage)
        jax.block_until_ready(_offer_holdout(
            mon, rng, next(mkeys), 0, np.zeros(0, np.int32),
            np.zeros(0, np.int32), np.zeros(0, np.float32),
            rspec.reservoir).res_users)
        def _drift_snapshot():
            if sharded:
                msst, mid_shard, mid_slot, _ = backend._pub
                idm = np.zeros(msst.shard_count * msst.capacity, np.int32)
                sid = mid_shard * msst.capacity + mid_slot
                idm[:len(sid)] = sid
                return monitor.holdout_snapshot_sharded(
                    mon, msst.sstate, jnp.asarray(idm), tomb=msst.tomb,
                    tombstone_frac=backend.tombstone_frac)
            mst = backend._pub[0]
            return monitor.holdout_snapshot(
                mon, mst.bstate, tomb=mst.tomb,
                tombstone_frac=backend.tombstone_frac)

        def _remap_reservoir(mon, table):
            """Renumber reservoir triples across a swap; deleted users'
            withheld ratings leave the holdout with them."""
            filled = int(mon.res_filled)
            ru = np.asarray(mon.res_users)[:filled]
            ri = np.asarray(mon.res_items)[:filled]
            rr = np.asarray(mon.res_ratings)[:filled]
            nu = table[ru]
            keep = nu >= 0
            k = int(keep.sum())
            cap_r = mon.res_users.shape[0]
            pad = lambda src, dt: jnp.asarray(np.concatenate(
                [src[keep].astype(dt), np.zeros(cap_r - k, dt)]))
            return dataclasses.replace(
                mon, res_users=pad(nu, np.int32), res_items=pad(ri, np.int32),
                res_ratings=pad(rr, np.float32), res_filled=jnp.int32(k))

    eng = RequestEngine(backend, cfg, clock=time.perf_counter, obs=o)
    # warm one executable per (batch shape, kind) — the compile budget the
    # run is held to (x live buckets; folds may grow the bucket once)
    pub = backend.snapshot()
    for s in cfg.batch_shapes():
        z = np.zeros(s, np.int64)
        jax.block_until_ready(backend.predict_pairs(pub, z, z))
        _ti, _ts = backend.recommend_topn(pub, z, args.topn)
        jax.block_until_ready(_ts)
    # pre-warm the fold path outside the timed window: the first fold pays
    # the fold executables + the regrown-capacity read warms, and under
    # serialized launches (mesh) that compile would stall in-window reads
    backend.fold_in(np.asarray(_synth_ratings(rng, args.foldin, args.items)),
                    cfg.fold_bq)
    pub = backend.snapshot()
    if mutations:
        # pre-warm the write lane itself — AFTER the fold pre-warm, so the
        # executables compile at the regrown capacity every in-window write
        # will run at (the fold above is what crosses the bucket boundary).
        # A bitwise no-op self-update (rows rewritten with their current
        # values — the decremental repair recomputes identical graph rows)
        # compiles the update + repair + publish executables, and a
        # zero-valid remove compiles the tombstone scatter; the first
        # in-window mutation otherwise pays those compiles while reads
        # queue behind the mesh exec lock
        warm_ids = np.arange(8)
        if sharded:
            msst0, wsh, wsl, _ = backend._pub
            warm_rows = np.asarray(msst0.sstate.state.ratings)[
                wsh[warm_ids] * msst0.capacity + wsl[warm_ids]]
        else:
            warm_rows = backend._pub[0].bstate.host_ratings()[warm_ids]
        backend.apply_update(warm_ids, warm_rows)
        backend.apply_remove(np.zeros(0, np.int64))
        pub = backend.snapshot()

    # closed-loop synchronous baseline: the wave treatment — one padded
    # jitted call per request, each waiting for the previous; its capacity
    # anchors the auto rate and the printed comparison
    rq = np.random.default_rng(7)
    svc = []
    for _ in range(24):
        m = int(rq.integers(4, 17))
        u = np.zeros(cfg.pad_shape(m), np.int64)
        u[:m] = rq.integers(0, n0, m)
        it = np.zeros_like(u)
        it[:m] = rq.integers(0, args.items, m)
        t0 = time.perf_counter()
        jax.block_until_ready(backend.predict_pairs(pub, u, it))
        svc.append(time.perf_counter() - t0)
    sync = latency_stats(svc)
    sync_qps = 1.0 / float(np.mean(svc))
    rate = args.rate if args.rate > 0 else 2.0 * sync_qps
    print(f"sync baseline: {sync_qps:.0f} req/s closed-loop "
          f"({sync.brief()}) -> open-loop target {rate:.0f} req/s")

    fold_batches = [np.asarray(_synth_ratings(rq, args.foldin, args.items))
                    for _ in range(4)]
    prof = obslib.profile_trace(args.jax_profile)
    prof.__enter__()
    eng.start()
    reqs = []
    t_start = time.perf_counter()
    t_stop = t_start + args.duration
    next_arr = t_start
    fold_every = args.duration / 3.0
    next_fold = t_start + fold_every * 0.6
    next_probe = t_start + args.duration / 6.0
    next_pub = t_start + 0.5  # metrics-registry publish cadence (obs only)
    folds_sent = 0
    if o is not None and not mutations:
        obs_next_start = backend.n_users  # logical id of the next folded row
    if mutations:
        mut_every = args.duration / 4.0
        next_mut = t_start + mut_every * 0.4
        mut_wave = 0
        next_start = backend.n_users  # logical id of the next folded row
    while True:
        now = time.perf_counter()
        if now >= t_stop:
            break
        if mutations and now >= next_mut:
            # mutation traffic: a deterministic event wave (re-rate /
            # un-rate / delete) against still-live base users, riding the
            # write lane alongside the folds. Checked before arrivals — at
            # saturating --rate the arrivals branch never yields otherwise.
            # Waves stay <= 8 events so every update/remove batch pads to
            # the one pre-warmed mutation shape (no in-window compiles).
            ev = mutation_events(13, mut_wave, n0, args.items,
                                 n_events=min(8, max(2, n0 // 8)),
                                 rerate_frac=0.3, unrate_frac=0.2,
                                 delete_frac=0.5)
            mut_wave += 1
            sel = alive[ev["users"]]
            upd = sel & (ev["kinds"] != 2)
            rem = sel & (ev["kinds"] == 2)
            if upd.any():
                r = eng.submit("update", users=ev["users"][upd],
                               rows=ev["rows"][upd])
                if r is not None:
                    reqs.append(r)
            if rem.any():
                r = eng.submit("remove", users=ev["users"][rem])
                if r is not None:
                    reqs.append(r)
                    alive[ev["users"][rem]] = False
                    removed_ids.extend(int(u) for u in ev["users"][rem])
            next_mut += mut_every
            continue
        if now >= next_arr:
            m = int(rq.integers(4, 17))
            uu = rq.integers(0, n0, m)
            if rq.random() < 0.15:
                r = eng.submit("topn", users=uu)
            else:
                r = eng.submit("pair", users=uu,
                               items=rq.integers(0, args.items, m))
            if r is not None:
                reqs.append(r)
            next_arr += rq.exponential(1.0 / rate)
            continue
        if now >= next_fold and folds_sent < len(fold_batches):
            if mutations:
                # withhold a holdout slice for the drift reservoir; logical
                # ids are cumulative append order (the write lane is FIFO,
                # so drain order == submission order)
                train, hrows, hcols, hvals = _withhold(
                    rq, fold_batches[folds_sent], rspec.holdout_frac)
                eng.submit("fold", rows=train)
                mon = _offer_holdout(mon, rng, next(mkeys), next_start,
                                     hrows, hcols, hvals, rspec.reservoir)
                mon = monitor.observe_fold_in(
                    mon,
                    masked_similarity(jnp.asarray(train),
                                      backend._pub[0].landmarks, spec.d1),
                    jnp.int32(len(train)))
                next_start += len(train)
            elif o is not None:
                # obs lifecycle feed: same withheld-slice discipline as the
                # --mutations monitor, minus the write-path stats
                train, hrows, hcols, hvals = _withhold(
                    rq, fold_batches[folds_sent], obs_rspec.holdout_frac)
                eng.submit("fold", rows=train)
                obs_mon = _offer_holdout(obs_mon, rng, next(obs_keys),
                                         obs_next_start, hrows, hcols,
                                         hvals, obs_rspec.reservoir)
                obs_next_start += len(train)
            else:
                eng.submit("fold", rows=fold_batches[folds_sent])
            folds_sent += 1
            next_fold += fold_every
            continue
        if use_ivf and now >= next_probe:
            # retrieval health *under* load; the lock keeps the probe's
            # collective-dense program from interleaving with a read batch
            # on the shared per-device threads (see RequestEngine)
            with eng.exec_lock:
                rec, pq, ee = recall_probe()
            recalls.append(rec)
            probeds.append(pq)
            if ee is not None:
                ee_recalls.append(ee)
            next_probe += args.duration / 6.0
            continue
        if o is not None and now >= next_pub:
            # periodic registry publish: snapshots taken mid-window see
            # live queue depth / latency series, not just the final state
            eng.publish_metrics()
            next_pub += 0.5
            continue
        time.sleep(min(0.0005, max(0.0, next_arr - now)))
    for r in reqs:  # drain: every admitted request must complete
        if not r.done.wait(timeout=60.0):
            raise RuntimeError("admitted request never completed")
    t_last = max([r.t_done for r in reqs] or [t_start])
    eng.stop()
    prof.__exit__(None, None, None)

    # post-run bitwise audit against the final generation, solo replay
    for _ in range(8):
        m = int(rq.integers(1, 17))
        uu = rq.integers(0, backend.n_users, m)
        eng.submit("pair", users=uu, items=rq.integers(0, args.items, m))
        eng.submit("topn", users=uu)
    eng.pump_reads()
    checked, bad = eng.verify_sample(limit=16)

    stats = eng.stats()
    elapsed = max(t_last - t_start, 1e-9)
    sustained_qps = stats["reads_completed"] / elapsed
    rl = stats["read_latency"]
    print(f"engine: sustained {sustained_qps:.0f} QPS over {elapsed:.1f}s "
          f"({stats['reads_completed']} reads in {stats['batches']} batches, "
          f"mean {stats['mean_batch_rows']:.1f} rows, "
          f"pad {stats['pad_frac']:.0%})")
    print(f"latency under load: {rl.brief()} | admission: "
          f"shed_frac={stats['shed_frac']:.3f} "
          f"(queue_cap={cfg.queue_cap} rows)")
    overlap = ("fold launches serialized with reads — host-mesh "
               "collective safety" if backend.serialize_folds
               else "reads never waited on a write")
    print(f"fold lane: {stats['completed']['fold']} batches "
          f"(+{stats['folded_rows']} users -> gen {stats['generation']}, "
          f"U={backend.n_users}) fold {stats['fold_latency'].brief()} — "
          f"{overlap}")
    if mutations:
        print(f"write lane: {mut_wave} event waves -> "
              f"updates={stats['completed']['update']} "
              f"removes={stats['completed']['remove']} "
              f"(mutated_rows={stats['mutated_rows']}, "
              f"repaired_rows={stats['repaired_rows']}, "
              f"tombstone_frac={stats['tombstone_frac']:.3f})")
        # pre-compaction bar: no live row's neighbor list cites a dead row
        if sharded:
            msst = backend._pub[0]
            g = msst.sstate.state.graph
            tombv = np.asarray(msst.tomb)
            nvv = np.asarray(msst.sstate.n_valid)
            gid = np.arange(len(tombv))
            row_valid = (gid % msst.capacity) < nvv[gid // msst.capacity]
        else:
            mstt = backend._pub[0]
            g = mstt.bstate.state.graph
            tombv = np.asarray(mstt.tomb)
            row_valid = np.arange(len(tombv)) < int(mstt.bstate.n_valid)
        gi, gw = np.asarray(g.indices), np.asarray(g.weights)
        cites_dead = (tombv[gi] & (gw != 0))[row_valid & ~tombv]
        assert not cites_dead.any(), "live graph row cites a tombstoned row"
        assert int(backend._pub[0].dirty_count()) == 0, (
            "write lane published with unrepaired rows")
        # the drift monitor's verdict on the window's live traffic
        snap = _drift_snapshot()
        if o is not None:
            monitor.publish_snapshot(o.registry, snap)
        if math.isnan(pol.base_mae) and snap.holdout_count >= rspec.min_holdout:
            pol.base_mae = snap.mae
        fire, reasons = policy.decide(pol, rspec, snap)
        compact = policy.should_compact_tombstones(rspec, snap.tombstone_frac)
        print(f"drift monitor: mae={snap.mae:.3f} "
              f"holdout={snap.holdout_count} "
              f"foldin_frac={snap.foldin_frac:.2f} "
              f"tombstone_frac={snap.tombstone_frac:.3f} -> fire={fire} "
              f"({','.join(reasons) if reasons else 'healthy'}) "
              f"compact={compact}")
        if fire or compact:
            if fire:
                policy.on_fire(pol)
            n_pre = backend.n_users
            with eng.exec_lock:
                gen_new, table = backend.refresh()
            mon = _remap_reservoir(mon, table)
            post = _drift_snapshot()
            if o is not None:
                monitor.publish_snapshot(o.registry, post)
            policy.on_swap(pol, gen_new, post.mae, rspec)
            print(f"refresh swap: gen {gen_new}, compacted "
                  f"{int(np.sum(table[:n_pre] < 0))} tombstones, post-swap "
                  f"mae={post.mae:.3f} "
                  f"tombstone_frac={post.tombstone_frac:.3f}")
            assert backend.tombstone_frac == 0.0, "compaction left tombstones"
    print(f"bitwise vs solo replay: {checked} requests re-run, "
          f"{bad} mismatches | non-finite predictions: {stats['nonfinite']}")
    caps = sorted(backend.caps_used)
    counts = {name: fn._cache_size() - cache0[name]
              for name, fn in families.items()}
    budget = len(cfg.batch_shapes()) * len(caps)
    print(f"executables per request-path family: {counts} "
          f"(budget {budget}: {len(cfg.batch_shapes())} batch shapes x "
          f"buckets {caps})")
    assert max(counts.values()) <= budget, (
        f"recompile count {counts} exceeds shapes x buckets budget {budget}")
    if use_ivf:
        ee_note = (f" early-exit recall {np.mean(ee_recalls):.3f}"
                   if ee_recalls else "")
        print(f"ivf under load: {len(recalls)} probes, recall@{kk} "
              f"{[f'{r:.3f}' for r in recalls]} "
              f"probed/q={np.mean(probeds):.1f}/{retrieval.nprobe}{ee_note}"
              if recalls else "ivf under load: window too short for probes")
    if o is not None:
        # final registry state: engine counters/histograms, per-family
        # compile counts, the retrieval series (exact-mode stub when no
        # index is up), and the lifecycle drift snapshot — one export
        # carries all three groups (docs/observability.md)
        eng.publish_metrics()
        obslib.publish_compile_counts(o.registry, families, cache0)
        from repro.retrieval import publish_retrieval
        if use_ivf:
            publish_retrieval(
                o.registry, nprobe=retrieval.nprobe,
                clusters=index.n_clusters,
                probed_per_q=(float(np.mean(probeds)) if probeds
                              else float(retrieval.nprobe)),
                recall=(float(np.mean(recalls)) if recalls else rec0),
                early_exit=bool(args.early_exit),
                escalations=esc_count, probes=len(recalls))
        else:
            publish_retrieval(o.registry)
        if not mutations:
            pub_l = backend.snapshot()
            if sharded:
                osst, osh, osl, _ = pub_l
                oidm = np.zeros(osst.shard_count * osst.capacity, np.int32)
                osid = osh * osst.capacity + osl
                oidm[:len(osid)] = osid
                obs_snap = monitor.holdout_snapshot_sharded(
                    obs_mon, osst, jnp.asarray(oidm))
            else:
                obs_snap = monitor.holdout_snapshot(obs_mon, pub_l[0])
            monitor.publish_snapshot(o.registry, obs_snap)
        if args.trace_dir:
            tp = o.export_trace(args.trace_dir)
            print(f"obs: {len(o.tracer.events())} spans "
                  f"({o.tracer.dropped} dropped) -> {tp}")
        if args.metrics_json:
            mp = o.export_metrics(args.metrics_json)
            print(f"obs: metrics snapshot -> {mp}")
        obslib.uninstall()
    assert bad == 0, "micro-batched results diverged from solo execution"
    assert stats["nonfinite"] == 0, "non-finite predictions under load"
    if args.smoke:
        assert sustained_qps > 0, "engine completed no reads under load"
        assert rl.count > 0 and rl.p95_ms <= cfg.slo_ms, (
            f"read p95 {rl.p95_ms:.1f}ms breached the {cfg.slo_ms:.0f}ms "
            "SLO under load")
        assert stats["completed"]["fold"] >= 1, (
            "smoke run must exercise the fold lane")
        if mutations:
            assert stats["completed"]["update"] >= 1, (
                "smoke run drained no in-place updates")
            assert stats["completed"]["remove"] >= 1, (
                "smoke run drained no removals")
            assert removed_ids and stats["tombstone_frac"] > 0, (
                "mutation stream produced no tombstones")
        if use_ivf:
            assert recalls and float(np.mean(recalls)) >= IVF_RECALL_SLO, (
                f"ivf recall under load "
                f"{np.mean(recalls) if recalls else float('nan'):.3f} "
                f"< {IVF_RECALL_SLO}")
    print("cf engine: done")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "cf"), default="lm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=None,
                    help="lm: decode batch (default 4); cf: pairs/users per "
                    "request (default 256)")
    # lm flags
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--landmark", action="store_true",
                    help="lm: decode through O(n) landmark summaries")
    # cf flags
    ap.add_argument("--ckpt", default=None,
                    help="cf: artifact directory (fit+save here when empty; "
                    "default: fresh temp dir)")
    ap.add_argument("--users", type=int, default=8192)
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--waves", type=int, default=None,
                    help="cf: request waves (default 3; lifecycle default 8)")
    ap.add_argument("--requests", type=int, default=32,
                    help="cf: timed predict calls per wave")
    ap.add_argument("--foldin", type=int, default=64,
                    help="cf: new users folded in between waves; in "
                    "--lifecycle mode, the fold-in batch bucket size")
    ap.add_argument("--topn", type=int, default=10)
    # cf --lifecycle flags
    ap.add_argument("--lifecycle", action="store_true",
                    help="cf: replay a drifting stream through the bucketed "
                    "fit->serve->monitor->refresh loop (docs/lifecycle.md)")
    ap.add_argument("--arrivals", type=int, default=64,
                    help="lifecycle: new users arriving per wave")
    ap.add_argument("--min-bucket", type=int, default=256,
                    help="lifecycle: smallest capacity on the bucket schedule")
    ap.add_argument("--growth", type=float, default=2.0,
                    help="lifecycle: geometric bucket growth factor")
    ap.add_argument("--drift", type=float, default=1.0,
                    help="lifecycle: preference drift strength of the stream")
    ap.add_argument("--selection", default="coresets",
                    choices=("random", "dist_ratings", "coresets",
                             "coresets_random", "popularity"),
                    help="lifecycle: landmark selection for fit AND refresh "
                    "(coresets: reselection follows the drifted population)")
    ap.add_argument("--compact", action="store_true",
                    help="cf: store the artifact as uint16 ids + bf16 weights")
    ap.add_argument("--compact-serving", action="store_true",
                    help="lifecycle: after each refresh swap, serve (and "
                    "checkpoint) the compact uint16/bf16 graph while the "
                    "capacity fits uint16; widened back on growth "
                    "(lifecycle.policy.should_compact)")
    ap.add_argument("--mesh", default=None,
                    help="lifecycle: run the replay sharded over this mesh, "
                    "e.g. pod=2,data=4 (rows block-partitioned over all "
                    "listed axes). Under JAX_PLATFORMS=cpu the host "
                    "platform is forced to that many devices, so CI can "
                    "smoke a pod.")
    ap.add_argument("--graph-backend", default="auto",
                    choices=("auto", "dense", "streaming", "pallas", "ivf"))
    ap.add_argument("--retrieval", default="exact", choices=("exact", "ivf"),
                    help="lifecycle: neighbor retrieval for the serve path. "
                    "'ivf' keeps an IVF index over the landmark embedding "
                    "(repro.retrieval): fold-in appends to it, refresh "
                    "rebuilds it, the skew gate repacks it, and every wave "
                    "reports recall@k vs the exact path (docs/retrieval.md)")
    ap.add_argument("--nprobe", type=int, default=0,
                    help="retrieval=ivf: probed cells per query "
                    "(0 = n_clusters/4; == n_clusters is exact)")
    ap.add_argument("--clusters", type=int, default=0,
                    help="retrieval=ivf: k-means cells (0 = ~sqrt(U))")
    ap.add_argument("--early-exit", action="store_true",
                    help="retrieval=ivf: per-query adaptive probing — a "
                    "query stops once its top-k survived `patience` further "
                    "cells; wave stats report probed-cells/query "
                    "(docs/retrieval.md). Works on both the single-device "
                    "and --mesh paths (search_early_exit_sharded)")
    # cf --engine flags
    ap.add_argument("--engine", action="store_true",
                    help="cf: serve through the continuous micro-batching "
                    "request engine (repro.serving) — open-loop load "
                    "generator, admission control, async fold-in lane; with "
                    "--mesh, the shard_map query router (docs/serving.md)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="engine: target arrival rate in requests/s "
                    "(0 = auto: 2x the measured synchronous closed-loop "
                    "capacity)")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="engine: load-generation window in seconds "
                    "(smoke clamps to 4)")
    ap.add_argument("--mutations", action="store_true",
                    help="engine: open the write path — in-place rating "
                    "updates and GDPR removals ride the async write lane "
                    "alongside fold-ins, an engine-fed drift monitor "
                    "accumulates holdout/volume/tombstone stats from live "
                    "traffic, and the lifecycle policy's verdict can fire a "
                    "tombstone-compacting refresh (docs/mutation.md)")
    ap.add_argument("--trace-dir", default=None,
                    help="obs: write a Chrome trace-event JSON of the run "
                    "(engine batch/request spans, write lane, lifecycle "
                    "refresh/repair/compaction) into this directory "
                    "(docs/observability.md)")
    ap.add_argument("--metrics-json", default=None,
                    help="obs: write the unified metrics snapshot — engine, "
                    "retrieval, and lifecycle series — to this JSON file")
    ap.add_argument("--sample-rate", type=float, default=1.0,
                    help="obs: per-request span sampling rate in [0, 1] "
                    "(deterministic seeded sampler; per-batch and "
                    "background spans are always recorded while tracing "
                    "is enabled)")
    ap.add_argument("--jax-profile", default=None,
                    help="obs: capture a jax.profiler device trace of the "
                    "engine load window into this directory")
    args = ap.parse_args(argv)
    if args.mutations and not args.engine:
        raise SystemExit("--mutations rides the request engine's write "
                         "lane; add --engine (--workload cf)")
    if args.retrieval == "ivf" and not (args.lifecycle or args.engine):
        raise SystemExit("--retrieval ivf runs on the lifecycle replay or "
                         "the request engine (--workload cf --lifecycle / "
                         "--engine); add --mesh to route probes through the "
                         "sharded posting lists")
    if args.mesh and os.environ.get("JAX_PLATFORMS") == "cpu":
        # must precede first backend use: on the CPU platform, force a
        # host-platform device count big enough for the mesh (no-op when
        # XLA_FLAGS already forces one). An accelerator run gets no fake
        # devices: a mesh larger than the chips present fails below.
        _, sizes = _parse_mesh(args.mesh)
        flags = os.environ.get("XLA_FLAGS", "")
        if "device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count="
                f"{int(np.prod(sizes))} " + flags)
    use_compile_cache()
    if args.batch is None:
        args.batch = 256 if args.workload == "cf" else 4
    if args.waves is None:
        args.waves = 8 if args.lifecycle else 3
    args.requests = max(1, args.requests)  # the wave loops time at least one

    if args.workload == "cf":
        if args.engine:
            _serve_cf_engine(args)
        elif args.lifecycle and args.mesh:
            _serve_cf_lifecycle_sharded(args)
        elif args.lifecycle:
            _serve_cf_lifecycle(args)
        else:
            _serve_cf(args)
    else:
        _serve_lm(args)


if __name__ == "__main__":
    main()
