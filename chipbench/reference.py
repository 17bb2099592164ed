"""The plain float64 reference and the comparison that decides ``correct``.

The reference functions are copied from ``chip_smoke.py`` (PR 11), which
checked the serving path on the chip with them: landmark-CF with cosine
d1 over co-rated items, cosine d2 top-k with the (weight desc, id asc)
order, and the top-N error; :class:`Comparison` computes the paper's
Eq. (1) as ``chip_smoke.ref_pairs`` and ``ref_scores`` do, over the rows
it gathers. They import nothing of ``repro`` and take
nothing the program made: the landmarks are chosen here by popularity,
and the representation is computed here from the ratings.

What the program returns is held to these numbers, each with its limit
from the configuration (``limits``):

- ``rows_err``: rows the served state holds that the reference does not,
  or the other way round (folds appended, removals tombstoned); exact.
- ``rep_err``: the widest gap of the served d1 representation from the
  reference, over every live row; infinite where ``rows_err`` is not 0.
- ``weight_err``: the widest gap of a served neighbour weight from the
  reference similarity of that pair.
- ``order_err``: how far the best candidate left out of a served
  neighbour list beats the worst one in it, by reference similarity;
  infinite for an id that is out of range, removed, repeated or the row
  itself.
- ``pair_err``: the widest gap of a served Eq. (1) prediction from the
  reference over the served neighbour list.
- ``topn_err``: the widest gap of a served top-N score, or of the best
  unrated item left out over the list's last.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

EPS = 1e-8  # the epsilon of the system's similarity and Eq. (1) denominators
NUMBERS = ("rows_err", "rep_err", "weight_err", "order_err", "pair_err", "topn_err")


# ------------------------------------------------- copied from chip_smoke.py
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
# ------------------------------------------------------------------------


def landmarks(counts: np.ndarray, n: int) -> np.ndarray:
    """The ``n`` users with most ratings, ties to the lower id."""
    return np.argsort(-counts, kind="stable")[:n]


def sparse_representation(r: sp.csr_matrix, lm: np.ndarray) -> np.ndarray:
    """:func:`ref_representation` over a sparse rating matrix."""
    m = r.copy()
    m.data = np.ones_like(m.data)
    mm = (lm != 0).astype(np.float64)
    z = r @ lm.T
    x = r.multiply(r).tocsr() @ mm.T
    y = m @ (lm * lm).T
    c = m @ mm.T
    sim = z / np.maximum(np.sqrt(x) * np.sqrt(y), EPS)
    return np.where(c > 1, sim, 0.0)


class Reference:
    """The deployment as the reference sees it, write by write.

    Rows start as the seed's rating matrix; ``update`` replaces a row,
    ``fold`` appends one and ``remove`` zeroes one and takes it out of the
    live set. The landmark basis stays the rows chosen at the start, as
    the program's stays frozen between refreshes."""

    def __init__(self, users, items, values, n_users: int, n_items: int,
                 n_landmarks: int):
        self.base = sp.csr_matrix(
            (values.astype(np.float64), (users, items)),
            shape=(n_users, n_items))
        self.n_items = n_items
        counts = np.diff(self.base.indptr)
        self.landmark_idx = landmarks(counts, n_landmarks)
        self.lm = self.base[self.landmark_idx].toarray()
        self.rep = sparse_representation(self.base, self.lm)
        sums = np.asarray(self.base.sum(1)).ravel()
        self.means = np.where(counts > 0, sums / np.maximum(counts, 1), 0.0)
        self.live = np.ones(n_users, bool)
        self.changed = {}  # row -> dense float64 row, for rows written

    @property
    def n_rows(self) -> int:
        return len(self.live)

    def rows(self, ids) -> np.ndarray:
        """Dense float64 rating rows of ``ids``."""
        ids = np.asarray(ids)
        out = np.zeros((len(ids), self.n_items))
        base = ids < self.base.shape[0]
        if base.any():
            out[base] = self.base[ids[base]].toarray()
        for j, u in enumerate(ids.tolist()):
            if u in self.changed:
                out[j] = self.changed[u]
        return out

    def counts(self) -> np.ndarray:
        """Ratings per row, every row the reference holds."""
        out = np.zeros(self.n_rows, np.int64)
        out[:self.base.shape[0]] = np.diff(self.base.indptr)
        for u, row in self.changed.items():
            out[u] = int((row != 0).sum())
        return out

    def values(self, ids: np.ndarray, items: np.ndarray) -> np.ndarray:
        """Ratings at (``ids``, ``items``), float64, any equal shapes."""
        ids, items = np.asarray(ids), np.asarray(items)
        out = np.zeros(ids.shape)
        base = ids < self.base.shape[0]
        if base.any():
            out[base] = np.asarray(self.base[ids[base], items[base]]).ravel()
        for u, row in self.changed.items():
            at = ids == u
            if at.any():
                out[at] = row[items[at]]
        return out

    def _set(self, u: int, row: np.ndarray) -> None:
        row = np.asarray(row, np.float64)
        self.changed[u] = row
        self.rep[u] = ref_representation(row[None], self.lm)[0]
        cnt = (row != 0).sum()
        self.means[u] = row.sum() / cnt if cnt else 0.0

    def apply(self, kind: str, user: int, row=None) -> None:
        if kind == "fold":
            assert user == self.n_rows, (user, self.n_rows)
            self.live = np.append(self.live, True)
            self.rep = np.vstack([self.rep, np.zeros((1, self.lm.shape[0]))])
            self.means = np.append(self.means, 0.0)
            self._set(user, row)
        elif kind == "update":
            self._set(user, row)
        elif kind == "remove":
            self._set(user, np.zeros(self.n_items))
            self.live[user] = False
        else:
            raise ValueError(kind)


class Comparison:
    """The numbers of one run, each the worst over what was checked."""

    def __init__(self):
        self.values = {k: 0.0 for k in NUMBERS}
        self.counts = {k: 0 for k in NUMBERS}

    def worse(self, name: str, value: float, n: int = 1) -> None:
        v = float(value)
        if np.isnan(v):
            v = np.inf
        self.values[name] = max(self.values[name], v)
        self.counts[name] += n

    def state(self, ref: Reference, served: dict) -> bool:
        """The served row set, and the d1 representation of every live
        row; False where the row sets differ (nothing more to compare)."""
        nv = served["n_valid"]
        n = min(nv, ref.n_rows)
        wrong = abs(nv - ref.n_rows) + int(
            (~served["tomb"][:n] != ref.live[:n]).sum())
        self.worse("rows_err", wrong)
        if wrong:
            self.worse("rep_err", np.inf)
            return False
        live = ref.live
        self.worse("rep_err", np.abs(served["rep"][:nv][live]
                                     - ref.rep[live]).max(initial=0.0),
                   int(live.sum()))
        return True

    def graph(self, ref: Reference, served: dict, rows: np.ndarray):
        """Served neighbour lists of ``rows``; returns the reference
        similarities of the listed pairs, (len(rows), k)."""
        ids = served["idx"][rows].astype(np.int64)
        k = ids.shape[1]
        n = ref.n_rows
        ok = (ids >= 0) & (ids < n)
        safe = np.clip(ids, 0, n - 1)
        ok &= ref.live[safe] & (safe != rows[:, None])
        srt = np.sort(safe, axis=1)
        ok_rows = ok.all(1) & (np.diff(srt, axis=1) != 0).all(1)
        sims = ref_sims(ref.rep, rows, safe)
        self.worse("weight_err",
                   np.abs(served["w"][rows] - sims).max(initial=0.0),
                   rows.size)
        if not ok_rows.all():
            self.worse("order_err", np.inf)
            return sims
        # best unlisted candidate over the worst listed one, per row
        ref_ids, ref_w, _ = ref_topk(ref.rep, k + k, ref.live, rows)
        listed = (ref_ids[:, :, None] == safe[:, None, :]).any(-1)
        best_out = np.where(listed, -np.inf, ref_w).max(1)
        gap = best_out - sims.min(1)
        self.worse("order_err", max(0.0, float(gap.max(initial=0.0))),
                   len(rows))
        return sims

    def reads(self, ref: Reference, served: dict, reads: list) -> None:
        """Pair and top-N answers served at one generation, against Eq. (1)
        over the served lists with the reference weights."""
        users = np.unique(np.concatenate([np.asarray(r["users"])
                                          for r in reads]))
        sims = self.graph(ref, served, users)
        row_of = np.full(max(ref.n_rows, served["n_valid"]), -1)
        row_of[users] = np.arange(len(users))
        nb = served["idx"].astype(np.int64)
        pair = [r for r in reads if r["kind"] == "pair"]
        if pair:
            u = np.concatenate([np.asarray(r["users"]) for r in pair])
            v = np.concatenate([np.asarray(r["items"]) for r in pair])
            got = np.concatenate([np.asarray(r["result"]) for r in pair])
            ids, w = nb[u], sims[row_of[u]]
            rv = ref.values(ids, np.broadcast_to(v[:, None], ids.shape))
            m = rv != 0
            num = (w * (rv - ref.means[ids]) * m).sum(1)
            den = (np.abs(w) * m).sum(1)
            want = ref.means[u] + num / np.maximum(den, EPS)
            self.worse("pair_err", np.abs(got - want).max(initial=0.0),
                       len(u))
        topn = [r for r in reads if r["kind"] == "topn"]
        if topn:
            u = np.concatenate([np.asarray(r["users"]) for r in topn])
            items = np.concatenate([np.asarray(r["result"][0]) for r in topn])
            scores = np.concatenate([np.asarray(r["result"][1])
                                     for r in topn])
            for lo in range(0, len(u), 64):
                ub = u[lo:lo + 64]
                ids, w = nb[ub], sims[row_of[ub]]
                rr = ref.rows(ids.ravel()).reshape(ids.shape + (-1,))
                m = rr != 0
                num = np.einsum("bk,bkp->bp", w,
                                (rr - ref.means[ids][..., None]) * m)
                den = np.einsum("bk,bkp->bp", np.abs(w), m)
                ref_sc = ref.means[ub][:, None] + num / np.maximum(den, EPS)
                self.worse("topn_err", top_n_error(
                    items[lo:lo + 64], scores[lo:lo + 64], ref_sc,
                    ref.rows(ub) != 0), len(ub))

    def verdict(self, limits: dict) -> bool:
        return all(self.values[k] <= limits[k] for k in NUMBERS)
