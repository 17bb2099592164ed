"""One generator for every traffic mix: a mix is data, read from
``chipbench/traffic/<name>.json``.

Open loop (``"loop": "open"``): ``round(rate * seconds)`` requests due at
the order statistics of uniform times over the window, which is a Poisson
process held to that count, so every seed offers the same amount of work.
The kinds come in their exact shares, and each kind's row counts cycle
through its ``rows`` range, both in a seeded order.

Closed loop (``"loop": "closed"``): ``clients`` callers, each sending its
next request of ``rows`` users once the last one is answered, walking the
users in a seeded shuffled order.

Users of reads and updates are drawn by YCSB's scrambled Zipfian over the
deployment's users; candidate items by the data's item popularity.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from chipbench import data as datalib

READS = ("pair", "topn")
WRITES = ("update", "fold", "remove")
_FNV_OFFSET, _FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3


@dataclasses.dataclass
class Request:
    due: float                      # seconds from the window's start
    kind: str
    users: Optional[np.ndarray] = None
    items: Optional[np.ndarray] = None
    rows: Optional[np.ndarray] = None   # (m, P) float32 for update / fold


def fnv64(x: np.ndarray) -> np.ndarray:
    """YCSB's FNV-1a 64-bit hash of each integer, byte by byte."""
    h = np.full(len(x), _FNV_OFFSET, np.uint64)
    v = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * np.uint64(_FNV_PRIME)
            v = v >> np.uint64(8)
    return h


class ScrambledZipf:
    """YCSB's scrambled Zipfian over ``ids``: Zipf(theta) ranks, then each
    rank hashed onto an id, so the hot ids are spread over the id space."""

    def __init__(self, ids: np.ndarray, theta: float):
        self.ids = np.asarray(ids)
        n = len(self.ids)
        p = 1.0 / np.arange(1, n + 1) ** theta
        self._draw = datalib._alias_sampler(p / p.sum())
        self._slot = (fnv64(np.arange(n)) % np.uint64(n)).astype(np.int64)

    def __call__(self, rng, size: int) -> np.ndarray:
        return self.ids[self._slot[self._draw(rng, size)]]


def _distinct(rng, draw, m: int) -> np.ndarray:
    got = np.empty(0, np.int64)
    while len(got) < m:
        more = draw(rng, 2 * m)
        _, first = np.unique(np.concatenate([got, more]), return_index=True)
        got = np.concatenate([got, more])[np.sort(first)]
    return got[:m]


def _sizes(rng, lo: int, hi: int, count: int) -> np.ndarray:
    return rng.permutation(np.resize(np.arange(lo, hi + 1), count))


def _kinds(rng, mix: list, n: int) -> np.ndarray:
    counts = [int(round(m["share"] * n)) for m in mix]
    counts[0] += n - sum(counts)
    return rng.permutation(np.repeat([m["kind"] for m in mix], counts))


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              ratings: datalib.Ratings, landmark_idx: np.ndarray
              ) -> List[Request]:
    """The open-loop schedule of one run."""
    if mix["users"]["dist"] != "scrambled_zipf":
        raise ValueError(f"open loop draws users by scrambled_zipf, not "
                         f"{mix['users']['dist']!r}")
    rng = np.random.default_rng([seed, 1])
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    kinds = _kinds(rng, mix["mix"], n)
    spec = {m["kind"]: m for m in mix["mix"]}
    users = np.arange(ratings.n_users)
    pick_user = ScrambledZipf(users, mix["users"]["theta"])
    item_draw = datalib._alias_sampler(ratings.item_p)
    out = [Request(float(t), str(k)) for t, k in zip(due, kinds)]

    for kind in READS:
        reqs = [r for r in out if r.kind == kind]
        if not reqs:
            continue
        lo, hi = spec[kind]["rows"]
        who = pick_user(rng, len(reqs))
        for r, u, m in zip(reqs, who, _sizes(rng, lo, hi, len(reqs))):
            r.users = np.full(m, u, np.int64)
            if kind == "pair":
                r.items = _distinct(rng, item_draw, m)
    if any(r.kind in WRITES for r in out):
        _writes(rng, out, ratings, landmark_idx, pick_user, item_draw)
    return out


def _writes(rng, out, ratings, landmark_idx, pick_user, item_draw):
    """Write payloads, in due order: an update re-rates one item of the
    user's current row; a fold brings a new user's row; a remove takes a
    user who is neither a landmark nor read nor updated in the window."""
    import scipy.sparse as sp

    base = sp.csr_matrix((ratings.values, (ratings.users, ratings.items)),
                         shape=(ratings.n_users, ratings.n_items))
    scale = ratings.model["scale"]
    levels = np.arange(scale["min"], scale["max"] + 1e-9, scale["step"],
                       dtype=np.float32)
    current = {}
    lm = set(np.asarray(landmark_idx).tolist())
    writers = [r for r in out if r.kind == "update"]
    who = pick_user(rng, 4 * len(writers) + 16)
    who = [u for u in who.tolist() if u not in lm][:len(writers)]
    for r, u in zip(writers, who):
        row = current.get(u)
        if row is None:
            row = base[u].toarray()[0].astype(np.float32)
        row = row.copy()
        row[item_draw(rng, 1)[0]] = levels[rng.integers(len(levels))]
        current[u] = row
        r.users, r.rows = np.array([u], np.int64), row[None]
    folds = [r for r in out if r.kind == "fold"]
    if folds:
        rows = datalib.new_users(ratings, rng, len(folds))
        for r, row in zip(folds, rows):
            r.rows = row[None]
    removes = [r for r in out if r.kind == "remove"]
    if removes:
        touched = set(lm)
        for r in out:
            if r.users is not None:
                touched.update(np.asarray(r.users).tolist())
        free = np.setdiff1d(np.arange(ratings.n_users),
                            np.fromiter(touched, np.int64))
        if len(free) < len(removes):
            raise ValueError("too few untouched users to remove")
        for r, u in zip(removes, rng.choice(free, len(removes),
                                            replace=False)):
            r.users = np.array([u], np.int64)


def closed_loop_order(mix: dict, ratings: datalib.Ratings,
                      seed: int) -> np.ndarray:
    """The closed loop's walk over the users."""
    if mix["users"]["dist"] != "shuffled_walk":
        raise ValueError(f"closed loop walks users in a shuffled_walk, not "
                         f"{mix['users']['dist']!r}")
    return np.random.default_rng([seed, 1]).permutation(ratings.n_users)
