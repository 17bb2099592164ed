"""The backend the engine drives, wrapped for the benchmark.

``Served`` delegates to a backend (the program's, or the control in its
place) and adds what changes nothing that backend computes:

- a ``jax.profiler.TraceAnnotation`` around each call into it, so that a
  profiler trace shows what the host was doing while the device sat idle;
- it numbers each read call in its annotation (``chipbench/topn#12``) and
  keeps the user ids of each top-N call, so that the least work of the
  top-N batches inside a capture can be counted;
- it keeps, of the generations named in ``keep``, the arrays that the
  check reads (representation, neighbour lists, tombstones, row count),
  the first time a read batch takes that generation, so that the answers
  served at it can be checked against the reference after the window.

:func:`state_arrays` is the host copy of what it kept.
"""
from __future__ import annotations

import jax
import numpy as np

ANNOTATION = "chipbench/"


class Served:
    """``inner`` is the backend; ``view(pub)`` gives the arrays of one of
    its generations that the check reads (:func:`program_view` for the
    program's backend)."""

    def __init__(self, inner, view, keep=()):
        self.inner = inner
        self.view = view
        self.reset(keep)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def reset(self, keep=()) -> None:
        """Start a new window: what to keep, and nothing kept yet."""
        self.keep = set(keep)
        self.kept = {}
        self.calls = {"pair": 0, "topn": 0}
        self.topn_batches = []

    def snapshot(self):
        pub = self.inner.snapshot()
        gen = pub[-1]
        if gen in self.keep and gen not in self.kept:
            self.kept[gen] = self.view(pub)
        return pub

    def _name(self, kind: str) -> str:
        self.calls[kind] += 1
        return f"{ANNOTATION}{kind}#{self.calls[kind]}"

    def predict_pairs(self, pub, users, items):
        with jax.profiler.TraceAnnotation(self._name("pair")):
            return self.inner.predict_pairs(pub, users, items)

    def recommend_topn(self, pub, users, n):
        self.topn_batches.append(np.array(users))
        with jax.profiler.TraceAnnotation(self._name("topn")):
            return self.inner.recommend_topn(pub, users, n)

    def fold_in(self, rows, bq):
        with jax.profiler.TraceAnnotation(ANNOTATION + "fold"):
            return self.inner.fold_in(rows, bq)

    def apply_update(self, ids, rows):
        with jax.profiler.TraceAnnotation(ANNOTATION + "update"):
            return self.inner.apply_update(ids, rows)

    def apply_remove(self, ids):
        with jax.profiler.TraceAnnotation(ANNOTATION + "remove"):
            return self.inner.apply_remove(ids)


def program_view(pub):
    """The arrays of a ``MutableLocalBackend`` generation that the check
    reads, still on the device: holding them does not hold the ratings."""
    mst = pub[0]
    st = mst.bstate.state
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    return (st.representation, graph.indices, graph.weights, mst.tomb,
            mst.bstate.n_valid)


class _Logical:
    """A row-sharded array of a ``MutableShardedBackend`` generation, read
    on the host in logical row order: ``rows[u]`` is logical row ``u``'s
    sharded row (``shard * capacity + slot``, from the published tables).
    With ``ids``, its values are sharded row ids too, read back as logical
    ids (-1 for a row no logical id owns). The device array is held, not
    copied: nothing runs until the check reads it."""

    def __init__(self, arr, rows: np.ndarray, ids: bool = False):
        self.arr, self.rows, self.ids = arr, rows, ids

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.arr)[self.rows]
        if self.ids:
            logical = np.full(np.shape(self.arr)[0], -1, np.int64)
            logical[self.rows] = np.arange(len(self.rows))
            out = logical[out]
        return out if dtype is None else out.astype(dtype)


def sharded_view(pub):
    """:func:`program_view` of a ``MutableShardedBackend`` generation: the
    same five arrays, in logical id order through the generation's own
    ``id_shard`` / ``id_slot`` tables, neighbour ids as logical ids, and
    the logical row count."""
    msst, id_shard, id_slot, _ = pub
    st = msst.sstate.state
    graph = st.graph.to_full() if st.graph.is_compact else st.graph
    rows = id_shard.astype(np.int64) * msst.capacity + id_slot
    return (_Logical(st.representation, rows),
            _Logical(graph.indices, rows, ids=True),
            _Logical(graph.weights, rows), _Logical(msst.tomb, rows),
            len(rows))


def state_arrays(view) -> dict:
    """Host copy of a kept view. The rows are cut on the host: a slice on
    the device would compile anew for every row count."""
    rep, idx, w, tomb, n_valid = view
    nv = int(n_valid)
    return {"n_valid": nv, "rep": np.asarray(rep)[:nv],
            "idx": np.asarray(idx)[:nv], "w": np.asarray(w)[:nv],
            "tomb": np.asarray(tomb)[:nv]}
