"""Idle device time named after what the program's host threads were doing.

Reads a ``jax.profiler`` capture as ``trace_reduce.reduce`` does (the same
device intervals, the same window), and takes from the host plane both the
benchmark's spans (``chipbench/``) and the program's own (``repro/``: the
engine's read-thread and write-lane phases, docs/observability.md), each on
its thread's line. Each stretch of idle device time is named after the
innermost span open at its middle, chosen across threads by:

- a work span outranks a wait span (``WAITS``: the read thread waiting for
  traffic, for a batch to fill, or for the device);
- a span of the write lane (a thread that holds a ``WRITE_LANE`` span)
  outranks one of the read thread;
- then the span that started last.

A stretch with no span open is ``no_call_open``. Names are cut at ``#``
(``chipbench/pair#12`` is ``chipbench/pair``).
"""
from __future__ import annotations

import collections
import glob
import os
from pathlib import Path
from typing import Optional

import numpy as np

from chipbench.trace_reduce import NO_CALL, _union

PREFIXES = ("repro/", "chipbench/")
WAITS = ("repro/read.idle", "repro/read.fill", "repro/execute.device")
WRITE_LANE = ("repro/apply[", "repro/write.", "repro/repair.",
              "chipbench/update", "chipbench/fold", "chipbench/remove")


def reduce(path: str) -> dict:
    """``window_s``, ``busy_s`` and ``gaps`` (idle seconds by label) of the
    first device that ran anything, and ``repro_spans``, the number of the
    program's host spans in the capture."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    dev_iv, edges, lines = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            iv = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                  for line in plane.lines if line.name == "XLA Ops"
                  for ev in line.events]
            if iv:
                u = _union(np.asarray(iv, np.float64))
                dev_iv.append(u)
                edges += [u[0, 0], u[-1, 1]]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    edges += [ev.start_ns, ev.start_ns + ev.duration_ns]
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      ev.name))
                if spans:
                    lines.append(spans)
    n_repro = sum(s[2].startswith("repro/") for spans in lines
                  for s in spans)
    if not dev_iv:
        return {"window_s": 0.0, "busy_s": 0.0, "gaps": {},
                "repro_spans": n_repro}
    t0, t1 = min(edges), max(edges)
    busy = dev_iv[0]
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9,
            "gaps": label_gaps(busy, t0, t1, lines),
            "repro_spans": n_repro}


class _Line:
    """The spans of one host thread, for "innermost span open at t"."""

    def __init__(self, spans):
        spans = sorted(spans, key=lambda s: (s[0], -s[1]))
        self.start = np.asarray([s[0] for s in spans], np.float64)
        self.end = np.asarray([s[1] for s in spans], np.float64)
        self.name = [s[2].split("#")[0] for s in spans]
        self.write = any(n.startswith(WRITE_LANE) for n in self.name)
        # the enclosing span of each (spans on one thread nest)
        self.parent = np.full(len(spans), -1)
        stack = []
        for j in range(len(spans)):
            while stack and self.end[stack[-1]] <= self.start[j]:
                stack.pop()
            self.parent[j] = stack[-1] if stack else -1
            stack.append(j)

    def innermost(self, t: float) -> int:
        j = int(np.searchsorted(self.start, t, side="right")) - 1
        while j >= 0 and self.end[j] <= t:
            j = self.parent[j]
        return j


def label_gaps(busy: np.ndarray, t0: float, t1: float, lines) -> dict:
    """Idle seconds between the merged ``busy`` intervals of one device,
    from ``t0`` to ``t1`` (ns), by label; ``lines`` holds one list of
    ``(start_ns, end_ns, name)`` spans per host thread."""
    starts = np.concatenate([[t0], busy[:, 1]])
    ends = np.concatenate([busy[:, 0], [t1]])
    keep = ends > starts
    threads = [_Line(spans) for spans in lines]
    out = collections.Counter()
    for s, e in zip(starts[keep], ends[keep]):
        mid = 0.5 * (s + e)
        best, label = None, NO_CALL
        for th in threads:
            j = th.innermost(mid)
            if j < 0:
                continue
            name = th.name[j]
            key = (name not in WAITS, th.write, th.start[j])
            if best is None or key > best:
                best, label = key, name
        out[label] += (e - s) * 1e-9
    return dict(out)


def host_work_s(gaps: dict) -> float:
    """Idle seconds named after a host work span: the idle that the host
    path causes, as against waiting for traffic or for the device."""
    return sum(v for k, v in gaps.items() if k != NO_CALL and k not in WAITS)


def find_capture(window_s: float, runs_dir: Path) -> Optional[dict]:
    """The reduction of the capture under ``runs_dir`` whose window is
    ``window_s``, newest first: the run's own, which ``run.py`` removes
    only after its metrics are read."""
    found = glob.glob(os.path.join(runs_dir, "*", "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        red = reduce(path)
        if red["window_s"] == window_s:
            return red
    return None
