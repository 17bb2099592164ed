"""Profiling hooks — optional ``jax.profiler`` capture + executable
accounting.

Two concerns live here because both answer "what did the device actually
run":

  ``profile_trace(dir)``   a context manager that wraps a region in a
                           ``jax.profiler`` trace when ``dir`` is set (the
                           serve loop uses it around the warm load window
                           via ``--jax-profile``) and is a no-op
                           otherwise. A trace that was asked for and
                           cannot start or stop raises: a run that
                           silently lost its trace would be read as one
                           that had none to give.
  launch/compile counters  ``count_launch`` bumps per-family launch and
                           row counters (row throughput = rows / wall
                           time); ``publish_compile_counts`` snapshots the
                           per-entry-point jit cache sizes (the
                           ``_cache_size`` attribute every jitted family
                           exposes) into ``exec.<name>.compiles`` gauges —
                           the same quantity the serve smoke's compile
                           budget assert bounds.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

from repro.obs.registry import MetricsRegistry


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str]):
    """Capture a ``jax.profiler`` trace into ``trace_dir`` for the duration
    of the block; yields True iff a trace was asked for (and started)."""
    if not trace_dir:
        yield False
        return
    import jax.profiler

    jax.profiler.start_trace(trace_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()


def count_launch(registry: MetricsRegistry, family: str, rows: int) -> None:
    """One device-program launch of ``family`` covering ``rows`` rows."""
    registry.counter(f"exec.{family}.launches").inc()
    registry.counter(f"exec.{family}.rows").inc(rows)


def publish_compile_counts(registry: MetricsRegistry, families: Dict,
                           baseline: Optional[Dict[str, int]] = None) -> None:
    """Gauge ``exec.<name>.compiles`` = jit-cache growth of each entry
    point since ``baseline`` (the serve loop records cache sizes right
    after warmup, so the gauge counts *post-warm* compiles — ideally 0)."""
    baseline = baseline or {}
    for name, fn in families.items():
        size = getattr(fn, "_cache_size", None)
        if size is None:
            continue
        registry.gauge(f"exec.{name}.compiles").set(
            float(size() - baseline.get(name, 0)))
