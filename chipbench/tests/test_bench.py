"""The benchmark's own tests, at sizes the CPU holds. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests

- the data generator holds the published counts and follows the model of
  ``repro.data.ratings.synthesize``;
- the reference agrees with the one of ``chip_smoke.py`` it was copied
  from;
- the trace reduction reads a capture recorded on a TPU v5e;
- a run of the program comes out correct, and comes out not correct with
  the control in its place, or with the timed path broken underneath:
  an answer altered where it is produced, or a write that leaves the
  state as it was;
- a fit on the program's Pallas graph path comes out not correct once
  updates arrive, the fault that makes the configurations fit on the
  streaming path.
"""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import data as datalib  # noqa: E402
from chipbench import reference as reflib  # noqa: E402
from chipbench.tests.tiny import tiny_cell  # noqa: E402

SEED = 2**31 + 77  # above 32 signed bits, as run seeds may be


def _cfg(**d):
    base = dict(n_users=943, n_items=1682, n_ratings=100_000,
                min_per_user=20, max_per_user=737,
                scale={"min": 1, "max": 5, "step": 1})
    base.update(d)
    return {"data": base}


def test_data_counts_and_seed():
    cfg = _cfg()
    a, b = datalib.synthesize(cfg, SEED), datalib.synthesize(cfg, SEED)
    assert np.array_equal(a.users, b.users) and np.array_equal(a.values,
                                                                b.values)
    c = datalib.synthesize(cfg, SEED + 1)
    assert not np.array_equal(a.items, c.items)
    assert a.n_ratings == 100_000
    counts = a.counts()
    assert counts.min() >= 20 and counts.max() <= 737
    keys = a.users.astype(np.int64) * a.n_items + a.items
    assert len(np.unique(keys)) == len(keys)
    assert set(np.unique(a.values)) <= {1, 2, 3, 4, 5}
    half = datalib.synthesize(_cfg(scale={"min": 0.5, "max": 5,
                                          "step": 0.5}), SEED)
    assert set(np.unique(half.values)) <= set(np.arange(1, 11) / 2)


def test_data_follows_the_original_model():
    """Same generative model as ``synthesize``: mean rating, spread and
    the concentration of ratings on popular items agree; only the
    per-user minimum (which the original lacks) is new."""
    from repro.data.ratings import synthesize

    orig = synthesize("movielens100k", 5)
    ours = datalib.synthesize(_cfg(min_per_user=1, max_per_user=1682), 5)
    assert abs(orig.ratings.mean() - ours.values.mean()) < 0.1
    assert abs(orig.ratings.std() - ours.values.std()) < 0.1

    def top_share(items, n):
        c = np.sort(np.bincount(items, minlength=n))[::-1]
        return c[: n // 10].sum() / c.sum()

    assert abs(top_share(orig.items, 1682) - top_share(ours.items, 1682)) \
        < 0.05


def test_new_users_are_drawn_like_users():
    ratings = datalib.synthesize(_cfg(), SEED)
    rows = datalib.new_users(ratings, np.random.default_rng(1), 50)
    per_row = (rows != 0).sum(1)
    assert rows.shape == (50, 1682) and per_row.min() >= 20
    assert set(np.unique(rows[rows != 0])) <= {1, 2, 3, 4, 5}


def test_reference_matches_chip_smoke():
    """The copied reference equals the original, and the comparison reads
    the original's own answers as exact."""
    import chip_smoke

    rng = np.random.default_rng(0)
    r = rng.integers(0, 6, (300, 120)).astype(np.float64)
    r *= rng.random(r.shape) < 0.3
    u, i = np.nonzero(r)
    ref = reflib.Reference(u, i, r[u, i], 300, 120, 8)
    lm = r[ref.landmark_idx]
    rep = reflib.ref_representation(r, lm)
    assert np.array_equal(rep, chip_smoke.ref_representation(r, lm))
    assert np.allclose(ref.rep, rep, rtol=0, atol=1e-12)
    live = np.ones(300, bool)
    rows = np.arange(300)
    ids, wts, w_next = reflib.ref_topk(rep, 5, live, rows)
    want = chip_smoke.ref_topk(rep, 5, live, rows)
    assert all(np.array_equal(x, y) for x, y in zip((ids, wts, w_next),
                                                    want))
    means = chip_smoke.ref_means(r)
    served = {"n_valid": 300, "rep": rep, "idx": ids, "w": wts,
              "tomb": np.zeros(300, bool)}
    users = rng.integers(0, 300, 40)
    items = rng.integers(0, 120, 40)
    scores = chip_smoke.ref_scores(r, means, ids, wts, users[:8])
    scores[r[users[:8]] != 0] = -np.inf
    top = np.argsort(-scores, axis=1, kind="stable")[:, :10]
    reads = [{"kind": "pair", "users": users[j:j + 1],
              "items": items[j:j + 1],
              "result": chip_smoke.ref_pairs(r, means, ids, wts,
                                             users[j:j + 1],
                                             items[j:j + 1])}
             for j in range(40)]
    reads += [{"kind": "topn", "users": users[j:j + 1],
               "result": (top[j:j + 1],
                          np.take_along_axis(scores[j:j + 1],
                                             top[j:j + 1], 1))}
              for j in range(8)]
    comp = reflib.Comparison()
    assert comp.state(ref, served)
    comp.reads(ref, served, reads)
    assert comp.values["rows_err"] == 0
    for k in ("rep_err", "weight_err", "order_err", "pair_err", "topn_err"):
        assert comp.values[k] < 1e-12, (k, comp.values[k])


def test_trace_reduce_reads_a_chip_capture():
    from chipbench import trace_reduce

    path = Path(__file__).parent / "v5e_capture.xplane.pb"
    red = trace_reduce.reduce(str(path))
    assert 0 < red["busy_s"] < red["window_s"]
    assert any("recommend_topn" in m for m in red["modules"])
    assert any(a.startswith("chipbench/topn#") for a in red["annotations"])
    idle = sum(red["gaps"].values())
    assert abs(idle + red["busy_s"] - red["window_s"]) < 1e-6 * red[
        "window_s"] + 1e-9
    bd = trace_reduce.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


# ------------------------------------------------ the comparison, end to end
def _run(traffic, build_fn=None):
    from chipbench import run

    return run.run(tiny_cell(traffic), SEED, 2.0, False, require_chip=False,
                   build_fn=build_fn)


@pytest.mark.parametrize("traffic", ["read", "write50", "topn_batch"])
def test_program_is_correct(traffic):
    out = _run(traffic)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_control_is_not_correct():
    from chipbench import control

    out = _run("read", control.build_control)
    assert not out["correct"], out["checks"]


class _Broken:
    """The program's backend with one fault planted underneath."""

    def __init__(self, inner, fault):
        self._inner, self._fault = inner, fault

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __copy__(self):
        return _Broken(copy.copy(self._inner), self._fault)

    def predict_pairs(self, pub, users, items):
        out = self._inner.predict_pairs(pub, users, items)
        return out + 0.01 if self._fault == "answer" else out

    def recommend_topn(self, pub, users, n):
        items, scores = self._inner.recommend_topn(pub, users, n)
        if self._fault == "answer":
            return items, scores + 0.01
        return items, scores

    def _unchanged(self):
        mst, gen = self._inner._pub
        self._inner._pub = (mst, gen + 1)
        return gen + 1

    def fold_in(self, rows, bq):
        if self._fault == "stale":
            return self._unchanged()
        return self._inner.fold_in(rows, bq)

    def apply_update(self, ids, rows):
        if self._fault == "stale":
            return self._unchanged()
        return self._inner.apply_update(ids, rows)

    def apply_remove(self, ids):
        if self._fault == "stale":
            return self._unchanged()
        return self._inner.apply_remove(ids)


def _broken(fault):
    from chipbench import run

    def build(c, seed):
        backend, ratings, spec, t = run.build(c, seed)
        backend.inner = _Broken(backend.inner, fault)
        return backend, ratings, spec, t

    return build


@pytest.mark.parametrize("traffic,fault", [
    ("read", "answer"), ("topn_batch", "answer"), ("write50", "answer"),
    ("write50", "stale")])
def test_fault_is_not_correct(traffic, fault):
    out = _run(traffic, _broken(fault))
    assert not out["correct"], out["checks"]


def test_fit_on_the_pallas_graph_path_is_not_correct():
    """The program's Pallas graph path (interpreted off the chip) leaves
    each neighbour list in slot order; an update's back-patch assumes the
    canonical order and repeats ids. The configurations fit on the
    streaming path for that reason (PERF.md, Open questions)."""
    from chipbench import run

    c = tiny_cell("write50", n_users=1600)  # more rows than one kernel tile
    c.cfg["fit_graph_backend"] = "pallas"
    out = run.run(c, SEED, 2.0, False, require_chip=False)
    assert not out["correct"], out["checks"]
    assert out["checks"]["order_err"]["value"] > 1, out["checks"]


# ------------------------------------------------------- a sharded deployment
# Four host devices exist only if XLA is told so before JAX starts, so the
# sharded runs go to a child process; it prints one result line per run.
SHARDED_RUNS = r"""
import json, sys
sys.path[:0] = sys.argv[1:3]
from chipbench import run
from chipbench.served import sharded_view
from chipbench.tests.tiny import tiny_cell

def broken_view(pub):
    # logical row 0 read from row 1's slot
    msst, shard, slot, gen = pub
    shard, slot = shard.copy(), slot.copy()
    shard[0], slot[0] = shard[1], slot[1]
    return sharded_view((msst, shard, slot, gen))

def build_broken(c, seed):
    backend, ratings, spec, t = run.build(c, seed)
    backend.view = broken_view
    return backend, ratings, spec, t

c = tiny_cell("read")
c.cell = {"chips": 4}
c.cfg["serving"]["backend"] = "MutableShardedBackend"
for build_fn in (None, build_broken):
    out = run.run(c, int(sys.argv[3]), 2.0, False, require_chip=False,
                  build_fn=build_fn)
    print(json.dumps(out), flush=True)
"""


@pytest.fixture(scope="module")
def sharded_runs():
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "-c", SHARDED_RUNS, str(ROOT), str(ROOT / "src"),
         str(SEED)], env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    import json

    program, broken = [json.loads(line) for line in
                       done.stdout.strip().splitlines()[-2:]]
    return program, broken


def test_sharded_program_is_correct(sharded_runs):
    """``MutableShardedBackend`` on a ``pod=4`` mesh: built, warmed,
    driven and checked through the same path as one chip."""
    out = sharded_runs[0]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["rows_err"]["value"] == 0


def test_sharded_view_with_a_wrong_slot_is_not_correct(sharded_runs):
    out = sharded_runs[1]
    assert not out["correct"], out["checks"]
    failing = {k for k, v in out["checks"].items() if v["value"] > v["limit"]}
    assert failing & {"rows_err", "rep_err", "weight_err"}, out["checks"]


def test_local_build_makes_no_mesh(monkeypatch):
    """A one-chip configuration is built as before: no mesh, one device."""
    import jax
    from jax.sharding import Mesh

    from chipbench import run

    made = []
    new = Mesh.__new__

    def counting_new(cls, *args, **kw):
        made.append(args)
        return new(cls, *args, **kw)

    monkeypatch.setattr(Mesh, "__new__", counting_new)
    backend, _, _, _ = run.build(tiny_cell("read"), SEED)
    assert made == []
    arrays = jax.tree_util.tree_leaves(backend.inner.snapshot()[0])
    assert {d for a in arrays for d in a.devices()} == {jax.devices()[0]}


def test_sharded_build_refuses_a_graph_path_it_cannot_take():
    """``fit_distributed`` builds its graph on the streaming path only."""
    from chipbench import run

    c = tiny_cell("read")
    c.cell = {"chips": 4}
    c.cfg["serving"]["backend"] = "MutableShardedBackend"
    c.cfg["fit_graph_backend"] = "pallas"
    with pytest.raises(SystemExit, match="streaming path only"):
        run.build(c, SEED)


def test_stalls_name_the_thread_that_holds_the_interpreter_lock():
    """A thread that holds the lock, blocked and then computing, stalls
    the watcher: the witness process runs on, the computing thread's CPU
    covers its stall and not the blocked one's, and the stacks found after
    each stall name the holder's frame."""
    import ctypes
    import threading
    import time

    from chipbench import run

    go = threading.Event()

    def blocked_holder():
        go.wait()
        ctypes.PyDLL(None).usleep(200_000)  # holds the lock, no CPU
        time.sleep(0.3)

    def busy_holder():
        go.wait()
        time.sleep(0.6)
        7 ** 900_000  # one C call, with the lock
        time.sleep(0.3)

    stalls = run.Stalls()
    try:
        threads = [threading.Thread(target=f, name=f.__name__)
                   for f in (blocked_holder, busy_holder)]
        for th in threads:
            th.start()
        time.sleep(0.3)  # the watcher has read each thread's clock
        go.set()
        for th in threads:
            th.join()
        stalls.report(0.0, 100.0)
    finally:
        stalls.stop()
    long = [s for s in stalls.seen if s.lost >= 0.08]
    assert len(long) == 2, [(s.at, s.lost) for s in stalls.seen]
    blocked, busy = long
    assert not blocked.witness and not busy.witness
    assert blocked.threads["blocked_holder"] < 0.02
    assert busy.threads["busy_holder"] > 0.5 * busy.lost
    assert "in blocked_holder" in "".join(blocked.stacks["blocked_holder"])
    assert "in busy_holder" in "".join(busy.stacks["busy_holder"])
