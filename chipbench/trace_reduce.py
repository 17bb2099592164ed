"""Reduce a ``jax.profiler`` capture to the benchmark's device numbers.

Reads the ``.xplane.pb`` file with nothing but JAX. From each TPU device
plane it takes the ``XLA Ops`` line (one event per operation that ran)
and the ``XLA Modules`` line (one event per program run). From the host
plane it takes the spans that the benchmark opened around its calls into
the program (names starting with ``chipbench/``).

- ``busy_s``: the union of the operation intervals, averaged over the
  devices that ran anything, and ``device_busy_s`` that of each device
  plane by name; ``window_s``: from the first to the last event of the
  capture, host and device.
- ``ops``: device seconds per operation (the HLO name, ``%fusion.17``,
  without its text); ``modules``: device seconds
  per program name, and ``module_runs`` their count.
- ``gaps``: idle device seconds, each stretch of idle time named after the
  benchmark span open on the host at its middle (its name up to ``#``),
  or ``no_call_open``.
- ``annotations``: the names of the benchmark's host spans, in order.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Optional

import numpy as np

PREFIX = "chipbench/"
NO_CALL = "no_call_open"  # the host was outside every call into the program


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (start, end) intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def reduce(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops = collections.Counter()
    modules = collections.Counter()
    runs = collections.Counter()
    busy, dev_iv, edges = {}, [], []
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            iv = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        # "%fusion.17 = f32[...] fusion(...)": the op's name
                        ops[ev.name.split(" = ")[0]] += ev.duration_ns * 1e-9
                        iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        name = ev.name.split("(")[0]
                        modules[name] += ev.duration_ns * 1e-9
                        runs[name] += 1
            if iv:
                u = _union(np.asarray(iv, np.float64))
                busy[plane.name] = float((u[:, 1] - u[:, 0]).sum()) * 1e-9
                dev_iv.append(u)
                edges += [u[0, 0], u[-1, 1]]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    edges += [ev.start_ns, ev.start_ns + ev.duration_ns]
                    if ev.name.startswith(PREFIX):
                        host.append((ev.start_ns, ev.start_ns
                                     + ev.duration_ns, ev.name))
    if not dev_iv:
        return {"busy_s": 0.0, "device_busy_s": {}, "window_s": 0.0,
                "ops": {}, "modules": {},
                "module_runs": {}, "gaps": {}, "annotations": []}
    t0, t1 = min(edges), max(edges)
    host.sort()
    return {
        "busy_s": float(np.mean(list(busy.values()))),
        "device_busy_s": busy,
        "window_s": (t1 - t0) * 1e-9,
        "ops": dict(ops),
        "modules": dict(modules),
        "module_runs": dict(runs),
        "gaps": _gaps(dev_iv[0], t0, t1, host),
        "annotations": [name for _, _, name in host],
    }


def _gaps(busy: np.ndarray, t0: float, t1: float, host: list) -> dict:
    """Idle seconds of one device, by the host span open mid-gap."""
    starts = np.concatenate([[t0], busy[:, 1]])
    ends = np.concatenate([busy[:, 0], [t1]])
    keep = ends > starts
    hs = np.asarray([h[0] for h in host], np.float64)
    he = np.asarray([h[1] for h in host], np.float64)
    out = collections.Counter()
    for s, e in zip(starts[keep], ends[keep]):
        mid = 0.5 * (s + e)
        j = np.searchsorted(hs, mid, side="right") - 1
        label = (host[j][2].split("#")[0] if j >= 0 and he[j] >= mid
                 else NO_CALL)
        out[label] += (e - s) * 1e-9
    return dict(out)


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, and idle time by what the host was doing."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
