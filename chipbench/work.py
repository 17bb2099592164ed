"""The least work a request needs, counted from the data.

A top-N row for user ``u`` over its neighbour list ``N(u)`` needs, at
the least, to read every rating of its neighbours (value and item id),
every rating of ``u`` (to leave out what ``u`` has seen), the list's ids
and weights and the neighbours' means, and to write the ``n`` answers.
It needs two multiply-adds per neighbour rating: one for the numerator
and one for the denominator of Eq. (1). None of this depends on how the
program lays the ratings out, so a program that stores or reads less is
judged against the same count.
"""
from __future__ import annotations

import numpy as np

ID_BYTES = 4


def topn_rows(nnz: np.ndarray, idx: np.ndarray, users: np.ndarray,
              n: int, value_bytes: int = 4):
    """(operations, bytes) of the top-N rows of ``users``.

    ``nnz[u]`` is user ``u``'s rating count, ``idx`` the served (U, k)
    neighbour lists."""
    users = np.asarray(users)
    nb = idx[users]
    nb_ratings = float(nnz[nb].sum())
    own = float(nnz[users].sum())
    per_rating = value_bytes + ID_BYTES
    lists = nb.size * (ID_BYTES + 4 + 4)  # id, weight, neighbour mean
    answers = len(users) * n * (ID_BYTES + 4)
    return 4.0 * nb_ratings, (nb_ratings + own) * per_rating + lists + answers


def least_seconds(ops: float, nbytes: float, peak: dict):
    """The roofline time and which bound sets it."""
    t_ops, t_mem = ops / peak["flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def topn_roofline(ctx):
    """Share of the roofline, in %, of the top-N batches in the capture:
    their least time at the chip's peaks over the device time of the top-N
    programs. None without a capture or without a top-N batch in it."""
    from chipbench import peaks

    red = ctx.trace
    if not red:
        return None
    dev_s = sum(v for k, v in red["modules"].items() if "recommend_topn" in k)
    calls = [int(a.split("#")[1]) for a in red["annotations"]
             if a.startswith("chipbench/topn#")]
    rows = [e["args"]["rows"] for e in ctx.spans
            if e["name"] == "execute[topn]"]
    if not dev_s or not calls:
        return None
    peak = peaks.peaks(ctx.device_kind)
    least, bound = 0.0, {}
    for i in calls:
        users = ctx.topn_batches[i - 1][:rows[i - 1]]
        ops, nbytes = topn_rows(ctx.nnz, ctx.served["idx"], users, ctx.topn)
        t, b = least_seconds(ops, nbytes, peak)
        least += t
        bound[b] = bound.get(b, 0) + 1
    ctx.log(f"topn roofline: {len(calls)} batches in the capture, least "
            f"{least:.6e} s ({bound} bound), device {dev_s:.6e} s in "
            + ", ".join(k for k in red["modules"] if "recommend_topn" in k))
    return 100.0 * least / dev_s
