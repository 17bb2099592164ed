"""A deployment's rating matrix, made from a seed.

The generative model is that of ``repro.data.ratings.synthesize``, copied
here so that the benchmark's inputs cannot change with the program:

    r_uv = clip(round(mu + b_u + b_v + p_u . q_v + noise), scale)

with power-law user and item activity (exponents 0.8 and 0.9, shuffled
over ids). What differs is how the observed cells are drawn, so that a
deployment holds its source's published counts exactly:

- every user gets at least ``min_per_user`` ratings and at most
  ``max_per_user``; the rest of the ``n_ratings`` are shared out by user
  activity;
- each user's items are distinct and drawn by item popularity, in bulk
  NumPy rounds instead of a Python loop over cells;
- values are rounded to the source's rating step (1 star, or half stars).
"""
from __future__ import annotations

import dataclasses

import numpy as np

MU = 3.6
BIAS_SD = 0.35
LATENT_DIM = 8
NOISE = 0.6
USER_EXP = 0.8
ITEM_EXP = 0.9


@dataclasses.dataclass(frozen=True)
class Ratings:
    """Observed cells in COO form, plus what the traffic needs to know."""

    users: np.ndarray   # (N,) int32
    items: np.ndarray   # (N,) int32
    values: np.ndarray  # (N,) float32
    n_users: int
    n_items: int
    item_p: np.ndarray  # (P,) item popularity, sums to 1
    model: dict         # per-user and per-item model terms, for new users

    @property
    def n_ratings(self) -> int:
        return len(self.values)

    def counts(self) -> np.ndarray:
        return np.bincount(self.users, minlength=self.n_users)


def _activity(rng, n: int, exponent: float) -> np.ndarray:
    act = 1.0 / np.arange(1, n + 1) ** exponent
    rng.shuffle(act)
    return act / act.sum()


def allocate(rng, total: int, p: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Per-user counts: ``lo`` each, the rest by ``p``, none above ``hi``."""
    n = len(p)
    if not lo * n <= total <= hi * n:
        raise ValueError(f"{total} ratings cannot give {n} users "
                         f"{lo}..{hi} each")
    counts = lo + rng.multinomial(total - lo * n, p)
    while True:
        over = counts > hi
        if not over.any():
            return counts
        excess = int((counts[over] - hi).sum())
        counts[over] = hi
        room = np.where(counts < hi, p, 0.0)
        counts += rng.multinomial(excess, room / room.sum())


def _alias_sampler(p: np.ndarray):
    """Walker's alias table for draws by ``p``: two lookups per draw."""
    n = len(p)
    prob = p * n
    alias = np.zeros(n, np.int64)
    small = [i for i in range(n) if prob[i] < 1.0]
    large = [i for i in range(n) if prob[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        alias[s] = g
        prob[g] -= 1.0 - prob[s]
        (small if prob[g] < 1.0 else large).append(g)
    for i in small + large:
        prob[i] = 1.0

    def draw(rng, size: int) -> np.ndarray:
        k = rng.integers(0, n, size)
        return np.where(rng.random(size) < prob[k], k, alias[k])

    return draw


def _heavy_rows(rng, rows: np.ndarray, need: np.ndarray, item_p: np.ndarray):
    """Keys of rows that take a large share of the catalogue: weighted
    sampling without replacement by exponential keys (Efraimidis-Spirakis),
    the ``need[u]`` smallest of ``E / p`` in each row."""
    n_items = len(item_p)
    out = []
    for lo in range(0, len(rows), 256):
        blk = rows[lo:lo + 256]
        key = rng.exponential(size=(len(blk), n_items)) / item_p
        order = np.argsort(key, axis=1)
        take = np.arange(n_items)[None, :] < need[blk][:, None]
        out.append((blk[:, None] * n_items + order)[take])
    return np.concatenate(out) if out else np.empty(0, np.int64)


def distinct_items(rng, counts: np.ndarray, item_p: np.ndarray):
    """Row ``u`` gets ``counts[u]`` distinct items drawn by ``item_p``.

    Rows that want more than an eighth of the catalogue are drawn by
    :func:`_heavy_rows`. For the others, each round draws with replacement
    for the rows still short, pools the draws with what those rows hold,
    and keeps a random subset of the distinct items of each row. Returns
    (users, items) sorted by user."""
    n_users, n_items = len(counts), len(item_p)
    draw = _alias_sampler(item_p)
    need = counts.astype(np.int64)
    heavy = need > n_items // 8
    done = [_heavy_rows(rng, np.flatnonzero(heavy), need, item_p)]
    held = np.empty(0, np.int64)       # keys of rows still short
    short = np.flatnonzero(~heavy)
    while len(short):
        have = np.bincount(held // n_items, minlength=n_users)[short]
        draws = np.ceil((need[short] - have) * 1.25).astype(np.int64) + 8
        uu = np.repeat(short, draws)
        keys = np.unique(np.concatenate([held, uu * n_items
                                         + draw(rng, len(uu))]))
        owner = keys // n_items
        # a random order inside each row, then the first counts[u] of it
        order = np.argsort((owner << 24) | rng.integers(0, 1 << 24,
                                                        len(keys)))
        keys, owner = keys[order], owner[order]
        first = np.searchsorted(owner, owner, side="left")
        keys = keys[np.arange(len(keys)) - first < need[owner]]
        got = np.bincount(keys // n_items, minlength=n_users)
        full = got[keys // n_items] == need[keys // n_items]
        done.append(keys[full])
        held = keys[~full]
        short = np.flatnonzero((got < need) & np.isin(np.arange(n_users),
                                                      short))
    keys = np.sort(np.concatenate(done))
    return (keys // n_items).astype(np.int32), (keys % n_items).astype(np.int32)


def _values(rng, mu_u, b_v, p_u, q_v, scale: dict) -> np.ndarray:
    raw = mu_u + b_v + np.einsum("nd,nd->n", p_u, q_v)
    raw = raw + rng.normal(0.0, NOISE, len(raw))
    step = float(scale["step"])
    vals = np.rint(raw / step) * step
    return np.clip(vals, scale["min"], scale["max"]).astype(np.float32)


def synthesize(cfg: dict, seed: int) -> Ratings:
    """The rating matrix of a deployment ``cfg`` (its ``data`` group)."""
    d = cfg["data"]
    n_users, n_items = d["n_users"], d["n_items"]
    rng = np.random.default_rng([seed, 0])
    u_p = _activity(rng, n_users, USER_EXP)
    i_p = _activity(rng, n_items, ITEM_EXP)
    counts = allocate(rng, d["n_ratings"], u_p, d["min_per_user"],
                      d["max_per_user"])
    users, items = distinct_items(rng, counts, i_p)
    b_u = rng.normal(0.0, BIAS_SD, n_users)
    b_v = rng.normal(0.0, BIAS_SD, n_items)
    p = rng.normal(0.0, 1.0 / np.sqrt(LATENT_DIM), (n_users, LATENT_DIM))
    q = rng.normal(0.0, 1.0, (n_items, LATENT_DIM))
    vals = _values(rng, MU + b_u[users], b_v[items], p[users], q[items],
                   d["scale"])
    model = {"b_v": b_v, "q": q, "counts": counts, "scale": d["scale"]}
    return Ratings(users, items, vals, n_users, n_items, i_p, model)


def new_users(data: Ratings, rng, m: int) -> np.ndarray:
    """``m`` rating rows of users who are not in the matrix yet, (m, P).

    Each is drawn as the matrix's users were: a rating count taken from a
    random existing user, items by popularity, values from fresh user
    terms under the same item terms."""
    md = data.model
    counts = rng.choice(md["counts"], m)
    users, items = distinct_items(rng, counts, data.item_p)
    b_u = rng.normal(0.0, BIAS_SD, m)
    p = rng.normal(0.0, 1.0 / np.sqrt(LATENT_DIM), (m, LATENT_DIM))
    vals = _values(rng, MU + b_u[users], md["b_v"][items], p[users],
                   md["q"][items], md["scale"])
    rows = np.zeros((m, data.n_items), np.float32)
    rows[users, items] = vals
    return rows
