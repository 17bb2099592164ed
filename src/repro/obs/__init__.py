"""Unified observability: metrics registry + request tracing + profiling.

One container object (:class:`Observability`) bundles the three
substrates — a :class:`~repro.obs.registry.MetricsRegistry`, a
:class:`~repro.obs.trace.Tracer`, and the profiling hooks — and is either
threaded explicitly (``RequestEngine(..., obs=o)``) or made current so
deep subsystems that have no parameter path to the serve loop (mutation
repair drains, the background refresh thread) can emit spans and counters
via :func:`current` / :func:`span`. Current is, first, the instance a
caller scoped to its own thread (:func:`scoped`: the engine's write lane
does so around each traced write) and, failing that, the process-wide
one of :func:`install` (the refresh thread).

The disabled configuration costs nothing on hot paths: producers guard on
``tracer.active`` (one attribute read) and the engine's own bounded
histograms/plain-int counters are always on regardless — the registry is
only written at ``publish_metrics`` time. ``DISABLED`` is the canonical
inert instance; the zero-overhead test monkeypatches its tracer with
raising sentinels and runs live traffic to prove no code path touches it.

Series naming convention (dotted prefixes, one registry):
``engine.*`` request path · ``retrieval.*`` ANN sidecar · ``lifecycle.*``
drift monitor + refresh · ``mutation.*`` write path · ``exec.*``
per-executable launch/compile accounting.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import math
import os
import time
from typing import Optional

from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import Sampler, Tracer
from repro.obs.profile import (
    count_launch,
    profile_trace,
    publish_compile_counts,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Sampler",
    "Tracer", "Observability", "DISABLED", "install", "uninstall",
    "current", "scoped", "span", "count_launch", "profile_trace",
    "publish_compile_counts",
]


class Observability:
    """Registry + tracer + export, one handle."""

    def __init__(self, *, sample_rate: float = 1.0, seed: int = 0,
                 max_events: int = 200_000, enabled: bool = True) -> None:
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.tracer = Tracer(sample_rate=sample_rate, seed=seed,
                             max_events=max_events, active=enabled)

    def export_trace(self, trace_dir: str, name: str = "trace.json") -> str:
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, name)
        self.tracer.export(path)
        return path

    def export_metrics(self, path: str) -> str:
        """Strict-JSON metrics snapshot (non-finite floats → null)."""
        snap = _sanitize(self.registry.snapshot())
        with open(path, "w") as f:
            json.dump(snap, f, indent=1, allow_nan=False)
        return path


def _sanitize(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _sanitize(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_sanitize(v) for v in x]
    return x


DISABLED = Observability(enabled=False)

_current: Optional[Observability] = None
# per thread (each thread starts from an empty context): what scoped() set
_scoped: contextvars.ContextVar = contextvars.ContextVar("repro_obs",
                                                         default=None)


def install(obs: Observability) -> None:
    """Make ``obs`` the process-wide current instance (for subsystems with
    no parameter path from the serve loop)."""
    global _current
    _current = obs


def uninstall() -> None:
    global _current
    _current = None


def current() -> Optional[Observability]:
    """The instance scoped to this thread, else the installed one."""
    o = _scoped.get()
    return _current if o is None else o


@contextlib.contextmanager
def scoped(obs: Optional[Observability]):
    """Make ``obs`` current for the calling thread for the block, without
    touching the process-wide instance of :func:`install`."""
    token = _scoped.set(obs)
    try:
        yield obs
    finally:
        _scoped.reset(token)


@contextlib.contextmanager
def span(name: str, cat: str = "bg", args: Optional[dict] = None,
         obs: Optional[Observability] = None):
    """Record the block as one phase span on ``obs`` (default:
    :func:`current`), a ``repro/<name>`` profiler annotation while it runs.
    ``args`` is read when the block ends, so the block may fill it in.
    No-op when nothing is current or tracing is off — subsystems wrap
    coarse regions (a write's phases, a repair drain, a refit), so the
    disabled cost is one generator frame per region, never per-request."""
    o = current() if obs is None else obs
    if o is None or not o.tracer.active:
        yield None
        return
    ph = o.tracer.phase(name, cat, time.monotonic, args)
    try:
        yield o
    finally:
        o.tracer.complete_many([ph.end()])
