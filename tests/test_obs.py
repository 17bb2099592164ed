"""Unified observability layer: histogram bucket-boundary exactness and
merge algebra, registry publish/delta/export semantics, seeded-sampler
determinism, span parent/ordering invariants under concurrent submit, the
zero-overhead-when-disabled contract, the per-kind shed counters +
queue gauges the engine publishes, and the read-thread and write-lane
phases: that they tile the read thread's time, nest as documented, record
under the engine's obs without ``install()``, and sit in a
``jax.profiler`` capture as ``repro/`` annotations of the same durations.

The engine-backed tests reuse the test_serving_engine.py fixture shape
(tiny fitted state, LocalBackend) — single-device, runs anywhere.
"""
import glob
import json
import math
import os
import threading
import time

import pytest

# Same idiom as the other serving tests: force the multi-device host
# platform before jax initialises, so this file composes with them in one
# pytest process regardless of collection order.
if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import check_obs  # noqa: E402
from repro import obs as obslib  # noqa: E402
from repro.core import LandmarkSpec, RatingMatrix  # noqa: E402
from repro.core.landmark_cf import fit  # noqa: E402
from repro.lifecycle import buckets  # noqa: E402
from repro.obs import (  # noqa: E402
    Histogram,
    MetricsRegistry,
    Observability,
    Sampler,
    Tracer,
)
from repro.serving import (  # noqa: E402
    EngineConfig,
    LocalBackend,
    MutableLocalBackend,
    RequestEngine,
)

SPEC = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
U, P = 64, 24


def _ratings(u, p, density=0.35, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(1, 6, (u, p)).astype(np.float32)
    r *= rng.random((u, p)) < density
    return r


@pytest.fixture(scope="module")
def state():
    r = _ratings(U, P, seed=3)
    return fit(jax.random.PRNGKey(0), RatingMatrix(jnp.asarray(r), U, P), SPEC)


def _local_backend(state):
    return LocalBackend(buckets.from_state(state, min_bucket=U), SPEC,
                        min_bucket=U)


# --------------------------------------------------------------- histogram


def test_histogram_bucket_boundary_exactness():
    """Bucket i covers (edges[i-1], edges[i]]: a value equal to an edge
    lands in that edge's OWN bucket, never the next one."""
    h = Histogram(lo=1.0, hi=16.0, growth=2.0)
    np.testing.assert_allclose(h.edges, [1.0, 2.0, 4.0, 8.0, 16.0])
    assert len(h.counts) == len(h.edges) + 1  # overflow slot
    h.record(1.0)       # == edges[0] -> bucket 0
    h.record(0.25)      # below lo    -> bucket 0 (open left tail)
    h.record(2.0)       # == edges[1] -> bucket 1, NOT bucket 2
    h.record(1.5)       # inside (1, 2] -> bucket 1
    h.record(2.0001)    # just past the edge -> bucket 2
    h.record(16.0)      # == top edge -> last real bucket
    h.record(16.0001)   # past top edge -> overflow slot
    assert list(h.counts) == [2, 2, 1, 0, 1, 1]
    assert h.count == 7 == int(h.counts.sum())
    assert h.vmin == 0.25 and h.vmax == 16.0001
    assert abs(h.total - (1.0 + 0.25 + 2.0 + 1.5 + 2.0001 + 16.0 + 16.0001)) < 1e-9


def test_histogram_percentile_within_one_bucket_width():
    """percentile(q) must stay within one multiplicative bucket width of
    the exact inverted_cdf order statistic."""
    growth = 2 ** 0.125
    rng = np.random.default_rng(5)
    vals = np.exp(rng.normal(1.0, 1.5, 5000))  # spans many buckets
    h = Histogram(lo=1e-3, hi=6e4, growth=growth)
    for v in vals:
        h.record(float(v))
    for q in (10.0, 50.0, 90.0, 95.0, 99.0, 100.0):
        exact = float(np.percentile(vals, q, method="inverted_cdf"))
        approx = h.percentile(q)
        assert exact / growth <= approx <= exact * growth, (
            f"q={q}: approx {approx} vs exact {exact}")
    assert math.isnan(Histogram().percentile(50.0))


def test_histogram_merge_associative_and_geometry_checked():
    rng = np.random.default_rng(9)

    def filled(seed_vals):
        h = Histogram(lo=1.0, hi=64.0, growth=2.0)
        for v in seed_vals:
            h.record(float(v))
        return h

    a_vals, b_vals, c_vals = (rng.uniform(0.5, 80.0, n) for n in (40, 25, 60))
    left = filled(a_vals).merge(filled(b_vals)).merge(filled(c_vals))   # (a+b)+c
    bc = filled(b_vals).merge(filled(c_vals))
    right = filled(a_vals).merge(bc)                                    # a+(b+c)
    swapped = filled(c_vals).merge(filled(a_vals)).merge(filled(b_vals))
    for other in (right, swapped):
        assert np.array_equal(left.counts, other.counts)
        assert left.count == other.count
        assert left.vmin == other.vmin and left.vmax == other.vmax
        assert abs(left.total - other.total) < 1e-6
    with pytest.raises(ValueError, match="geometry"):
        filled(a_vals).merge(Histogram(lo=1.0, hi=128.0, growth=2.0))


def test_registry_publish_idempotent_and_delta():
    reg = MetricsRegistry()
    live = Histogram(lo=1.0, hi=16.0, growth=2.0)
    for v in (1.5, 3.0, 9.0):
        live.record(v)
    reg.publish_histogram("engine.latency_ms.pair", live)
    reg.publish_histogram("engine.latency_ms.pair", live)  # republish
    snap = reg.snapshot()
    h = snap["histograms"]["engine.latency_ms.pair"]
    assert h["count"] == 3 and sum(h["counts"]) == 3  # no double count
    c = reg.counter("engine.batches")
    c.inc(3)
    s0 = reg.snapshot()
    c.inc(2)
    live.record(12.0)
    reg.publish_histogram("engine.latency_ms.pair", live)
    d = reg.delta(s0)
    assert d["counters"]["engine.batches"] == 2
    assert d["histograms"]["engine.latency_ms.pair"]["count"] == 1
    reg.gauge("engine.queue_rows").set(7.0)
    prom = reg.to_prometheus()
    assert "# TYPE engine_batches counter" in prom
    assert "engine_queue_rows 7" in prom
    assert 'engine_latency_ms_pair_bucket{le="+Inf"} 4' in prom


# ----------------------------------------------------------------- sampler


def test_sampler_seeded_determinism():
    n = 2000
    s1, s2 = Sampler(0.3, seed=7), Sampler(0.3, seed=7)
    seq1 = [s1.sample() for _ in range(n)]
    seq2 = [s2.sample() for _ in range(n)]
    assert seq1 == seq2  # same seed + rate -> identical accept sequence
    frac = sum(seq1) / n
    assert 0.25 < frac < 0.35
    other = [Sampler(0.3, seed=8).sample() for _ in range(n)]
    assert other != seq1  # different seed -> different sequence
    assert all(Sampler(1.0, seed=0).sample() for _ in range(50))
    assert not any(Sampler(0.0, seed=0).sample() for _ in range(50))
    # the tracer's lock-free fast path agrees with the sampler edges
    assert Tracer(sample_rate=1.0).should_sample()
    assert not Tracer(sample_rate=0.0).should_sample()
    t1 = Tracer(sample_rate=0.3, seed=7)
    t2 = Tracer(sample_rate=0.3, seed=7)
    assert ([t1.should_sample() for _ in range(n)]
            == [t2.should_sample() for _ in range(n)] == seq1)


def test_tracer_bounded_buffer_counts_drops():
    tr = Tracer(max_events=5)
    for i in range(8):
        tr.complete(f"s{i}", "bg", 0.0, 1.0)
    assert len(tr.events()) == 5 and tr.dropped == 3
    tr2 = Tracer(max_events=3)
    tr2.complete_many([{"name": f"s{i}", "cat": "bg", "t0": 0.0, "t1": 1.0}
                       for i in range(5)])
    assert len(tr2.events()) == 3 and tr2.dropped == 2


def test_span_contextmanager_and_install():
    o = Observability(sample_rate=1.0, seed=0)
    obslib.install(o)
    try:
        assert obslib.current() is o
        with obslib.span("repair_drain", cat="mutation",
                         args={"rows": 4}) as got:
            assert got is o
        evs = o.tracer.events()
        assert [e["name"] for e in evs] == ["repair_drain"]
        assert evs[0]["cat"] == "mutation" and evs[0]["args"] == {"rows": 4}
        assert evs[0]["t1"] >= evs[0]["t0"]
    finally:
        obslib.uninstall()
    assert obslib.current() is None
    with obslib.span("ignored") as got:  # nothing installed -> no-op
        assert got is None
    assert len(o.tracer.events()) == 1
    # explicit obs= overrides the (absent) installed instance
    with obslib.span("explicit", obs=o):
        pass
    assert [e["name"] for e in o.tracer.events()] == ["repair_drain",
                                                      "explicit"]


# ------------------------------------------- engine spans under concurrency


def test_span_parent_ordering_under_concurrent_submit(state):
    """Every sampled request exports one root serve[...] span with a unique
    id and exactly two children (queued + exec/apply) citing it as parent,
    children nested inside the root interval, queued ending where exec
    begins — under genuinely concurrent threaded submission."""
    backend = _local_backend(state)
    cfg = EngineConfig(max_batch=16, min_shape=4, queue_cap=4096,
                       max_wait_ms=0.5, slo_ms=500.0, fold_bq=8, topn=5)
    o = Observability(sample_rate=1.0, seed=0)
    eng = RequestEngine(backend, cfg, obs=o)
    eng.start()
    rng = np.random.default_rng(2)
    fold_rows = _ratings(4, P, seed=11)
    reqs, lock = [], threading.Lock()

    def client(tseed):
        trng = np.random.default_rng(tseed)
        mine = []
        for _ in range(12):
            m = int(trng.integers(1, 5))
            uu = trng.integers(0, U, m)
            if trng.random() < 0.5:
                r = eng.submit("pair", users=uu, items=trng.integers(0, P, m))
            else:
                r = eng.submit("topn", users=uu)
            assert r is not None
            r.done.wait(10.0)
            mine.append(r)
        with lock:
            reqs.extend(mine)

    threads = [threading.Thread(target=client, args=(100 + i,))
               for i in range(4)]
    for t in threads:
        t.start()
    fr = eng.submit("fold", rows=fold_rows)
    for t in threads:
        t.join()
    assert fr is not None and fr.done.wait(10.0)
    eng.stop()

    evs = o.tracer.events()
    assert o.tracer.dropped == 0
    roots = [e for e in evs if e["name"].startswith("serve[")]
    kids = [e for e in evs if "parent" in e]
    assert len(roots) == len(reqs) + 1  # 48 reads + 1 fold, rate 1.0
    ids = [e["id"] for e in roots]
    assert len(set(ids)) == len(ids)  # unique span ids
    by_parent = {}
    for k in kids:
        by_parent.setdefault(k["parent"], []).append(k)
    assert set(by_parent) == set(ids)  # every child cites a real root
    for root in roots:
        children = sorted(by_parent[root["id"]], key=lambda e: e["t0"])
        assert [c["name"] for c in children] in (["queued", "exec"],
                                                 ["queued", "apply"])
        q, x = children
        # nesting: children inside the root interval, handoff at pickup
        assert root["t0"] <= q["t0"] <= q["t1"] <= x["t1"] <= root["t1"]
        assert q["t1"] == x["t0"]  # queued ends exactly at exec pickup
        assert root["t0"] == q["t0"]
        assert root["t1"] == x["t1"]
    # batch-level spans exist independently of request sampling
    cats = {e["cat"] for e in evs}
    assert {"engine", "request", "write"} <= cats
    execs = [e for e in evs if e["name"].startswith("execute[")]
    assert sum(e["args"]["rows"] for e in execs) == sum(
        r.n_rows for r in reqs)


def test_sampling_rate_bounds_request_spans(state):
    backend = _local_backend(state)
    cfg = EngineConfig(max_batch=16, min_shape=4, queue_cap=4096,
                       slo_ms=500.0, topn=5)
    o = Observability(sample_rate=0.25, seed=3)
    eng = RequestEngine(backend, cfg, obs=o)
    n = 64
    for i in range(n):
        assert eng.submit("pair", users=[i % U], items=[i % P]) is not None
    eng.pump_reads()
    roots = [e for e in o.tracer.events() if e["name"].startswith("serve[")]
    assert 0 < len(roots) < n  # sampled, not all, not none
    # batch spans are NOT sampled away — capacity accounting stays exact
    execs = [e for e in o.tracer.events()
             if e["name"].startswith("execute[")]
    assert sum(e["args"]["rows"] for e in execs) == n


# ----------------------------------------------------- zero overhead / off


def test_zero_overhead_when_disabled(state):
    """An engine without obs must never touch the tracer: DISABLED's
    tracer methods are replaced with raising sentinels, live traffic runs,
    and the shared registry stays empty."""
    backend = _local_backend(state)
    eng = RequestEngine(backend, EngineConfig(max_batch=16, min_shape=4,
                                              queue_cap=256, slo_ms=500.0,
                                              fold_bq=8, topn=5))
    tr = obslib.DISABLED.tracer
    assert eng.obs is None and eng._tracer is tr and not tr.active

    def boom(*a, **k):
        raise AssertionError("disabled tracer was invoked on the hot path")

    saved = {m: getattr(tr, m) for m in
             ("complete", "complete_many", "should_sample", "new_id",
              "phase")}
    for m in saved:
        setattr(tr, m, boom)
    try:
        rng = np.random.default_rng(4)
        for _ in range(10):
            m = int(rng.integers(1, 5))
            assert eng.submit("pair", users=rng.integers(0, U, m),
                              items=rng.integers(0, P, m)) is not None
        eng.submit("fold", rows=_ratings(2, P, seed=13))
        eng.pump_reads()
        eng.pump_folds()
        eng.publish_metrics()  # no obs -> no-op
    finally:
        for m, fn in saved.items():
            setattr(tr, m, fn)
    assert len(tr.events()) == 0 and tr.dropped == 0
    assert obslib.DISABLED.registry.empty()
    # latency accounting still happened in the always-on bounded histograms
    assert eng.latencies["pair"].count == 10
    assert eng.latencies["fold"].count == 1


def test_engine_latencies_are_bounded_histograms(state):
    """Satellite (a): per-request latency memory is fixed regardless of
    traffic volume — no unbounded lists anywhere in the engine."""
    backend = _local_backend(state)
    eng = RequestEngine(backend, EngineConfig(max_batch=16, min_shape=4,
                                              queue_cap=4096, slo_ms=500.0,
                                              topn=5))
    h = eng.latencies["pair"]
    assert isinstance(h, Histogram)
    nbytes0 = h.counts.nbytes + len(h.edges)
    for i in range(300):
        assert eng.submit("pair", users=[i % U], items=[i % P]) is not None
        if i % 37 == 0:
            eng.pump_reads()
    eng.pump_reads()
    assert h.count == 300
    assert h.counts.nbytes + len(h.edges) == nbytes0  # fixed memory
    st = eng.stats()
    assert st["read_latency"].count == 300
    assert st["read_latency"].p99_ms >= st["read_latency"].p50_ms


# ------------------------------------------------- shed counters and gauges


def test_per_kind_shed_counters_and_queue_gauges(state):
    backend = _local_backend(state)
    cfg = EngineConfig(max_batch=8, min_shape=4, queue_cap=8, slo_ms=500.0,
                       fold_queue_cap=2, fold_bq=8, topn=5)
    o = Observability(sample_rate=0.0, seed=0)
    eng = RequestEngine(backend, cfg, obs=o)
    assert eng.submit("pair", users=[0] * 4, items=[0] * 4) is not None
    assert eng.submit("pair", users=[1] * 4, items=[1] * 4) is not None
    assert eng.submit("pair", users=[2] * 4, items=[2] * 4) is None  # shed
    assert eng.submit("topn", users=[3]) is None                     # shed
    for _ in range(2):
        assert eng.submit("fold", rows=_ratings(1, P, seed=21)) is not None
    assert eng.submit("fold", rows=_ratings(1, P, seed=22)) is None  # shed
    st = eng.stats()
    assert st["shed"] == {"pair": 1, "topn": 1, "fold": 1,
                          "update": 0, "remove": 0}
    assert st["shed_frac_by_kind"]["pair"] == pytest.approx(1 / 3)
    assert st["shed_frac_by_kind"]["topn"] == pytest.approx(1.0)
    assert st["shed_frac_by_kind"]["fold"] == pytest.approx(1 / 3)
    assert st["queue_rows"] == 8 and st["write_queue"] == 2
    eng.publish_metrics()
    snap = o.registry.snapshot()
    assert snap["counters"]["engine.shed.pair"] == 1
    assert snap["counters"]["engine.shed.fold"] == 1
    assert snap["counters"]["engine.shed.update"] == 0
    assert snap["gauges"]["engine.queue_rows"] == 8.0
    assert snap["gauges"]["engine.write_queue"] == 2.0
    eng.pump_reads()
    eng.pump_folds()
    eng.publish_metrics()
    snap = o.registry.snapshot()
    assert snap["gauges"]["engine.queue_rows"] == 0.0
    assert snap["gauges"]["engine.write_queue"] == 0.0
    assert 0.0 < snap["gauges"]["engine.row_occupancy"] <= 1.0
    # publish is idempotent: counters are absolute copies, not re-added
    eng.publish_metrics()
    assert o.registry.snapshot()["counters"]["engine.shed.pair"] == 1


# ------------------------------------------------------- export + validator


def test_exports_satisfy_ci_schema_checker(state, tmp_path):
    """End-to-end: run traffic, publish all three series groups, export,
    and validate with the exact checker CI runs (benchmarks.check_obs),
    including the read/fold-overlap requirement."""
    backend = _local_backend(state)
    cfg = EngineConfig(max_batch=16, min_shape=4, queue_cap=4096,
                       max_wait_ms=0.5, slo_ms=500.0, fold_bq=8, topn=5)
    o = Observability(sample_rate=1.0, seed=0)
    eng = RequestEngine(backend, cfg, obs=o)
    eng.start()
    stop = threading.Event()

    def read_load():
        rng = np.random.default_rng(6)
        while not stop.is_set():
            r = eng.submit("pair", users=rng.integers(0, U, 4),
                           items=rng.integers(0, P, 4))
            if r is not None:
                r.done.wait(5.0)

    t = threading.Thread(target=read_load)
    t.start()
    for i in range(3):
        fr = eng.submit("fold", rows=_ratings(6, P, seed=30 + i))
        assert fr is not None and fr.done.wait(10.0)
    stop.set()
    t.join()
    eng.stop()
    eng.publish_metrics()
    from repro.retrieval import publish_retrieval
    publish_retrieval(o.registry)
    o.registry.gauge("lifecycle.mae").set(0.9)
    o.registry.counter("lifecycle.holdout_count").set(12)
    tpath = o.export_trace(str(tmp_path))
    mpath = o.export_metrics(str(tmp_path / "metrics.json"))
    doc = check_obs.check_trace(tpath, require_overlap=True)
    check_obs.check_metrics(mpath)
    # the exported JSON is strict (no NaN/Inf literals)
    json.loads((tmp_path / "metrics.json").read_text(),
               parse_constant=lambda s: pytest.fail(f"non-strict {s}"))
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "execute[pair]" in names and "apply[fold]" in names


# ------------------------------------- phases, on the profiler's clock too

READ_PHASES = ("read.idle", "read.fill", "read.form", "exec_wait",
               "execute.dispatch", "execute.device", "execute.fetch",
               "read.scatter")
BATCH_PHASES = ("read.form", "execute.dispatch", "execute.device",
                "execute.fetch", "read.scatter")
WRITE_PHASES = ("write.prepare", "write.mutate", "repair.drain",
                "repair.round", "write.publish")
PHASE_CFG = EngineConfig(max_batch=16, min_shape=4, queue_cap=4096,
                         max_wait_ms=0.5, slo_ms=500.0, fold_bq=8, topn=5)


def _mutable_backend(state):
    return MutableLocalBackend(buckets.from_state(state, min_bucket=U), SPEC,
                               min_bucket=U)


def _most_cited(state) -> int:
    """The user most neighbour lists cite: updating it dirties rows."""
    idx = np.asarray(state.graph.indices)
    return int(np.bincount(idx[idx >= 0].ravel(), minlength=U).argmax())


def _drive(eng, seconds, rng, update=None):
    """Reads from this thread with pauses for ``seconds`` (the read thread
    idles between them), plus one ``update`` (users, rows) half-way."""
    reqs = []
    t_end = time.monotonic() + seconds
    t_write = time.monotonic() + seconds / 2
    while time.monotonic() < t_end:
        m = int(rng.integers(1, 5))
        users = rng.integers(0, U, m)
        if rng.random() < 0.7:
            reqs.append(eng.submit("pair", users=users,
                                   items=rng.integers(0, P, m)))
        else:
            reqs.append(eng.submit("topn", users=users))
        if update is not None and time.monotonic() > t_write:
            reqs.append(eng.submit("update", users=update[0],
                                   rows=update[1]))
            update = None
        time.sleep(0.004)
    for r in reqs:
        assert r is not None and r.done.wait(10.0)
    return reqs


def test_read_phases_tile_the_read_thread(state):
    """The read thread's phases cover its whole life, back to back."""
    o = Observability(sample_rate=0.0, seed=0)
    eng = RequestEngine(_local_backend(state), PHASE_CFG, obs=o)
    t0 = time.monotonic()
    eng.start()
    tid = eng._threads[0].ident
    _drive(eng, 0.8, np.random.default_rng(8))
    eng.stop()
    life = time.monotonic() - t0
    evs = sorted((e for e in o.tracer.events()
                  if e["tid"] == tid and e["name"] in READ_PHASES),
                 key=lambda e: e["t0"])
    assert {e["name"] for e in evs} == set(READ_PHASES)
    for a, b in zip(evs, evs[1:]):
        assert a["t1"] <= b["t0"], (a, b)  # no two phases overlap
    covered = sum(e["t1"] - e["t0"] for e in evs)
    assert 0.95 * life <= covered <= life, (covered, life)
    assert o.tracer.dropped == 0


def test_every_batch_has_one_of_each_phase(state):
    """Per batch: one of each phase, in order; ``execute[kind]`` (launch
    to answers on the host) holds dispatch, device and fetch; ``read.form``
    carries the queued rows at pickup."""
    o = Observability(sample_rate=0.0, seed=0)
    eng = RequestEngine(_local_backend(state), PHASE_CFG, obs=o)
    rng = np.random.default_rng(9)
    for i in range(40):
        users = rng.integers(0, U, int(rng.integers(1, 5)))
        if i % 3:
            r = eng.submit("pair", users=users,
                           items=rng.integers(0, P, len(users)))
        else:
            r = eng.submit("topn", users=users)
        assert r is not None
    queued = eng.stats()["queue_rows"]
    n = eng.pump_reads()
    assert n == eng.batches > 1
    evs = o.tracer.events()

    def of(name):
        return sorted((e for e in evs if e["name"] == name),
                      key=lambda e: e["t0"])

    execs = sorted((e for e in evs if e["name"].startswith("execute[")),
                   key=lambda e: e["t0"])
    assert len(execs) == n
    for name in BATCH_PHASES + ("exec_wait",):
        assert len(of(name)) == n, name
    for ex, form, wait, disp, dev, fetch, scat in zip(
            execs, of("read.form"), of("exec_wait"),
            of("execute.dispatch"), of("execute.device"),
            of("execute.fetch"), of("read.scatter")):
        times = [form["t0"], form["t1"], wait["t0"], wait["t1"], ex["t0"],
                 disp["t0"], disp["t1"], dev["t0"], dev["t1"], fetch["t0"],
                 fetch["t1"], ex["t1"], scat["t0"], scat["t1"]]
        assert times == sorted(times)
        assert set(ex["args"]) == {"rows", "shape", "gen", "batch"}
        assert form["args"]["queued"] >= ex["args"]["rows"]
    assert of("read.form")[0]["args"]["queued"] == queued


def test_write_lane_phases_record_under_the_engine_obs(state):
    """An update through the engine records its phases and the drain's
    counters on the engine's obs, with nothing installed process-wide."""
    o = Observability(sample_rate=1.0, seed=0)
    eng = RequestEngine(_mutable_backend(state), PHASE_CFG, obs=o)
    assert obslib.current() is None
    r = eng.submit("update", users=[_most_cited(state)],
                   rows=_ratings(1, P, seed=40))
    assert r is not None and eng.pump_folds() == 1
    assert obslib.current() is None
    evs = o.tracer.events()
    (apply,) = [e for e in evs if e["name"] == "apply[update]"]
    got = {name: [e for e in evs if e["name"] == name]
           for name in WRITE_PHASES}
    for name in ("write.prepare", "write.mutate", "repair.drain",
                 "write.publish"):
        assert len(got[name]) == 1, name
    (drain,) = got["repair.drain"]
    rounds = got["repair.round"]
    assert drain["args"]["rows"] > 0 and len(rounds) >= 1
    assert sum(e["args"]["rows"] for e in rounds) == drain["args"]["rows"]
    order = [got[name][0] for name in ("write.prepare", "write.mutate",
                                       "repair.drain", "write.publish")]
    for a, b in zip(order, order[1:]):
        assert a["t1"] <= b["t0"]
    for e in order + rounds:
        assert apply["t0"] <= e["t0"] <= e["t1"] <= apply["t1"]
    for e in rounds:
        assert drain["t0"] <= e["t0"] <= e["t1"] <= drain["t1"]
    counters = o.registry.snapshot()["counters"]
    assert counters["mutation.repair_rounds"] == len(rounds)
    assert counters["mutation.repaired_rows"] == drain["args"]["rows"]


def test_profiler_capture_holds_each_phase(state, tmp_path):
    """Each phase is a ``repro/`` annotation in a ``jax.profiler`` capture
    (Python tracer off, as the chip benchmark captures), as long as the
    tracer's record of it to within 50 us."""
    o = Observability(sample_rate=0.0, seed=0)
    eng = RequestEngine(_mutable_backend(state), PHASE_CFG, obs=o)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.start()
        _drive(eng, 0.4, np.random.default_rng(10),
               update=([_most_cited(state)], _ratings(1, P, seed=41)))
        eng.stop()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    captured = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro/"):
                    captured.setdefault(ev.name[len("repro/"):], []).append(
                        (ev.start_ns, ev.duration_ns * 1e-9))
    recorded = {}
    for e in o.tracer.events():
        recorded.setdefault(e["name"], []).append((e["t0"],
                                                   e["t1"] - e["t0"]))
    for name in READ_PHASES + WRITE_PHASES + ("apply[update]",):
        cap, rec = sorted(captured[name]), sorted(recorded[name])
        assert len(cap) == len(rec), name
        for (_, dc), (_, dr) in zip(cap, rec):
            assert abs(dc - dr) < 50e-6, (name, dc, dr)
