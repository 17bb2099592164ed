"""The control: the plain reference put in the program's place, computed
one precision below what the configuration states.

The configurations state float32 with matrix products at ``HIGHEST``. The
control computes the same landmark-CF answers in float32 with every
matrix product at ``HIGH``, the step down that a later change could be
tempted to take: three bfloat16 passes (high by high, high by low, low by
high, accumulated in float32), written out here so that it computes the
same on the CPU as on the chip. It is written from the reference's
definitions, not from the program: d1 over co-rated items against the
popularity landmarks, a full d2 top-k recomputed after every write, and
Eq. (1). Driven by the same engine and checked by the same comparison,
it has to come out as not correct; its readings are the upper ends from
which the limits are set.

``--lower`` names what is computed at ``HIGH``: ``graph`` (d1 and the d2
neighbour graph) and ``reads`` (Eq. (1) of the top-N lists; a pair
prediction has no matrix product). The rest is computed at ``HIGHEST``.
The default lowers both; ``--lower reads`` is the step that a change to
the read programs alone would take.

    python3 chipbench/control.py --workload ml1m.read --seeds 1,2,3 --seconds 5

prints one JSON line per seed with the numbers compared. Runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import data as datalib  # noqa: E402

EPS = 1e-8
BLOCK = 1024
LOWER = ("graph", "reads")


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _product(spec: str, a, b, high: bool):
    """``einsum(spec, a, b)`` at ``HIGH`` (three bfloat16 products) or,
    with ``high`` false, at ``HIGHEST``."""
    if not high:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    (ah, al), (bh, bl) = _split(a), _split(b)
    f = partial(jnp.einsum, spec, preferred_element_type=jnp.float32)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


@partial(jax.jit, static_argnames=("high",))
def _d1(r, lm, high):
    """Cosine over co-rated items of each row of ``r`` with each landmark."""
    dot = partial(_product, "ij,jk->ik", high=high)
    m, mm = (r != 0).astype(r.dtype), (lm != 0).astype(r.dtype)
    z, x = dot(r, lm.T), dot(r * r, mm.T)
    y, c = dot(m, (lm * lm).T), dot(m, mm.T)
    sim = z / jnp.maximum(jnp.sqrt(x) * jnp.sqrt(y), EPS)
    return jnp.where(c > 1, sim, 0.0)


@partial(jax.jit, static_argnames=("k", "high"))
def _graph(rep, live, k, high):
    """d2 cosine top-k of every row over the live rows, self excluded."""
    cap = rep.shape[0]
    norm = jnp.sqrt((rep * rep).sum(1))

    def block(b):
        rows = b * BLOCK + jnp.arange(BLOCK)
        q = jax.lax.dynamic_slice_in_dim(rep, b * BLOCK, BLOCK)
        qn = jax.lax.dynamic_slice_in_dim(norm, b * BLOCK, BLOCK)
        s = _product("ij,jk->ik", q, rep.T, high)
        s = s / jnp.maximum(qn[:, None] * norm[None], EPS)
        s = jnp.where(live[None] & (rows[:, None] != jnp.arange(cap)[None]),
                      s, -jnp.inf)
        w, i = jax.lax.top_k(s, k)
        return i.astype(jnp.int32), jnp.where(jnp.isfinite(w), w, 0.0)

    idx, w = jax.lax.map(block, jnp.arange(cap // BLOCK))
    return idx.reshape(cap, k), w.reshape(cap, k)


@jax.jit
def _pairs(r, means, idx, w, users, items):
    nb, wb = idx[users], w[users]
    rv = r[nb, items[:, None]]
    m = (rv != 0).astype(r.dtype)
    num = (wb * (rv - means[nb]) * m).sum(1)
    den = (jnp.abs(wb) * m).sum(1)
    return means[users] + num / jnp.maximum(den, EPS)


@partial(jax.jit, static_argnames=("n", "high"))
def _topn(r, means, idx, w, users, n, high):
    nb, wb = idx[users], w[users]
    rr = r[nb]
    m = (rr != 0).astype(r.dtype)
    num = _product("bk,bkp->bp", wb, (rr - means[nb][..., None]) * m, high)
    den = _product("bk,bkp->bp", jnp.abs(wb), m, high)
    pred = means[users][:, None] + num / jnp.maximum(den, EPS)
    pred = jnp.where(r[users] != 0, -jnp.inf, pred)
    scores, items = jax.lax.top_k(pred, n)
    return jnp.where(jnp.isfinite(scores), items, -1), scores


def _means(r):
    cnt = (r != 0).sum(1)
    return jnp.where(cnt > 0, r.sum(1) / jnp.maximum(cnt, 1), 0.0)


class ControlBackend:
    """A serving backend with the engine's interface, built on the
    reference's definitions, with the products that ``lower`` names at
    ``HIGH``. Every write recomputes the changed rows' d1 and the whole
    neighbour graph, then publishes."""

    serialize_folds = False

    def __init__(self, ratings: datalib.Ratings, capacity: int, spec,
                 lower=LOWER):
        self.k = spec.k_neighbors
        self.high_graph = "graph" in lower
        self.high_reads = "reads" in lower
        u, p = ratings.n_users, ratings.n_items
        cap = -(-capacity // BLOCK) * BLOCK
        r = jax.jit(lambda i, j, v: jnp.zeros((cap, p), jnp.float32)
                    .at[i, j].set(v))(ratings.users, ratings.items,
                                      ratings.values)
        counts = jnp.where(jnp.arange(cap) < u, (r != 0).sum(1), -1)
        lm_idx = jax.lax.top_k(counts, spec.n_landmarks)[1]
        self.lm = r[lm_idx]
        rep = jnp.concatenate([_d1(r[lo:lo + BLOCK], self.lm, self.high_graph)
                               for lo in range(0, cap, BLOCK)])
        tomb = jnp.zeros(cap, bool)
        self._pub = (self._publish(r, rep, tomb, u), 0)

    def _publish(self, r, rep, tomb, n_valid):
        live = (jnp.arange(r.shape[0]) < n_valid) & ~tomb
        idx, w = _graph(rep, live, self.k, self.high_graph)
        return {"r": r, "rep": rep, "idx": idx, "w": w, "tomb": tomb,
                "means": _means(r), "n_valid": jnp.int32(n_valid)}

    @property
    def generation(self) -> int:
        return self._pub[1]

    def snapshot(self):
        return self._pub

    @staticmethod
    def view(pub):
        """The arrays that the check reads, as ``served.program_view``."""
        s = pub[0]
        return s["rep"], s["idx"], s["w"], s["tomb"], s["n_valid"]

    def predict_pairs(self, pub, users, items):
        s = pub[0]
        return _pairs(s["r"], s["means"], s["idx"], s["w"],
                      jnp.asarray(users, jnp.int32),
                      jnp.asarray(items, jnp.int32))

    def recommend_topn(self, pub, users, n):
        s = pub[0]
        return _topn(s["r"], s["means"], s["idx"], s["w"],
                     jnp.asarray(users, jnp.int32), n, self.high_reads)

    def _write(self, ids, rows, dead=False) -> int:
        s, gen = self._pub
        ids = jnp.asarray(ids, jnp.int32)
        rows = jnp.asarray(rows, jnp.float32)
        r = s["r"].at[ids].set(rows)
        rep = s["rep"].at[ids].set(_d1(rows, self.lm, self.high_graph))
        tomb = s["tomb"].at[ids].set(dead) if dead else s["tomb"]
        n_valid = max(int(s["n_valid"]), int(ids.max()) + 1)
        self._pub = (self._publish(r, rep, tomb, n_valid), gen + 1)
        jax.block_until_ready(self._pub[0]["w"])
        return gen + 1

    def fold_in(self, rows, bq) -> int:
        n = int(self._pub[0]["n_valid"])
        return self._write(np.arange(n, n + len(rows)), rows)

    def apply_update(self, ids, rows) -> int:
        return self._write(ids, rows)

    def apply_remove(self, ids) -> int:
        p = self._pub[0]["r"].shape[1]
        return self._write(ids, np.zeros((len(ids), p), np.float32), True)


def build_control(c, seed: int, lower=LOWER):
    """``run.build``'s counterpart: the control in the program's place."""
    from chipbench.served import Served
    from repro.configs import registry

    t0 = time.monotonic()
    ratings = datalib.synthesize(c.cfg, seed)
    spec = registry.get(c.cfg["model"]).model
    backend = ControlBackend(ratings, c.cfg["serving"]["capacity"], spec,
                             lower)
    return (Served(backend, ControlBackend.view), ratings, spec,
            {"control": time.monotonic() - t0})


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--lower", default=",".join(LOWER),
                    help="what runs at HIGH: graph, reads, or both")
    args = ap.parse_args(argv)
    lower = tuple(args.lower.split(","))
    if not set(lower) <= set(LOWER):
        ap.error(f"--lower takes {LOWER}, not {lower}")
    c = run.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.run(c, seed, args.seconds, False,
                      build_fn=partial(build_control, lower=lower))
        print(json.dumps({"seed": seed, "lower": args.lower,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
