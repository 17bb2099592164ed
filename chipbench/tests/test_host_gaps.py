"""Tests of ``host_gaps``, the reduction that names idle device time after
the program's own host spans, and of the readers that use the program's
phases. Run by hand, like ``test_bench.py``:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests

- the labelling, on made-up intervals: innermost span, work over wait,
  write lane over read thread, ``no_call_open`` only where nothing is open;
- the committed capture of ``test_bench.py`` reduces as it always did
  (numbers pinned in ``v5e_capture.reduced.json``), and ``host_gaps`` finds
  the same window and device time in it;
- a capture recorded on a TPU v5e with the program's phases
  (``record_spans.py``) has its ``repro/`` spans read and its idle time
  named after them;
- a traced run of the tiny write cell on the CPU reports the new readers'
  program-span metrics.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import host_gaps, trace_reduce  # noqa: E402
from chipbench.tests.tiny import tiny_cell  # noqa: E402

SEED = 2**31 + 78


def test_label_gaps_innermost_and_precedence():
    # device busy [10, 20), [30, 40), [50, 60), [70, 80), [90, 100) in a
    # window [0, 120): idle stretches with middles 5, 25, 45, 65, 85, 110
    busy = np.asarray([[10, 20], [30, 40], [50, 60], [70, 80], [90, 100]],
                      np.float64)
    read = [
        (0, 10, "repro/read.idle"),
        (21, 49, "repro/execute[pair]"),        # parent ...
        (21, 24, "repro/execute.dispatch"),     # ... closed child at 25
        (24, 28, "repro/execute.device"),       # ... wait child at 25
        (40, 48, "repro/execute.fetch"),        # ... work child at 45
        (60, 70, "repro/read.fill"),            # wait, beside a write span
        (80, 89, "repro/read.form"),            # work, beside a write span
    ]
    write = [
        (55, 75, "repro/apply[update]"),
        (55, 62, "repro/write.mutate"),         # closed before 65
        (62, 75, "repro/repair.drain"),
        (63, 66, "repro/repair.round"),         # innermost at 65
        (82, 88, "chipbench/update#3"),         # write lane, at 85
    ]
    g = host_gaps.label_gaps(busy, 0, 120, [read, write])
    assert g == {
        "repro/read.idle": 10e-9,               # only a wait is open
        "repro/execute.device": 10e-9,          # innermost, not the parent
        "repro/execute.fetch": 10e-9,           # innermost, not last begun
        "repro/repair.round": 10e-9,            # work outranks the fill
        "chipbench/update": 10e-9,              # write lane outranks reads
        trace_reduce.NO_CALL: 20e-9,            # nothing open at 110
    }
    assert host_gaps.host_work_s(g) == pytest.approx(30e-9)


def test_committed_capture_reduces_as_before():
    """The capture of ``test_bench.py``: ``trace_reduce`` gives the numbers
    it gave when the capture was committed, and ``host_gaps`` the same
    window and device time; it holds no program span, so its idle time
    falls to the benchmark's calls and ``no_call_open`` as before."""
    path = HERE / "v5e_capture.xplane.pb"
    red = trace_reduce.reduce(str(path))
    pinned = json.loads((HERE / "v5e_capture.reduced.json").read_text())
    assert {k: red[k] for k in pinned} == pinned
    own = host_gaps.reduce(str(path))
    assert own["window_s"] == red["window_s"]
    assert own["busy_s"] == red["busy_s"]
    assert own["repro_spans"] == 0
    assert set(own["gaps"]) == set(red["gaps"])
    for k, v in red["gaps"].items():
        assert abs(own["gaps"][k] - v) < 1e-12
    # found by its window among captures in directories below one
    assert host_gaps.find_capture(red["window_s"], HERE.parent)[
        "window_s"] == red["window_s"]


def test_chip_capture_with_phases():
    """A capture of the tiny write cell on a TPU v5e with the program's
    phases: its ``repro/`` spans are read, idle time is named after them,
    and the host's share of it is at most the idle share."""
    path = HERE / "v5e_spans.xplane.pb"
    red = trace_reduce.reduce(str(path))
    own = host_gaps.reduce(str(path))
    assert own["repro_spans"] > 0
    assert own["window_s"] == red["window_s"]
    labels = set(own["gaps"])
    assert any(k.startswith("repro/") for k in labels), labels
    idle = sum(own["gaps"].values())
    assert abs(idle + own["busy_s"] - own["window_s"]) < 1e-6 * own[
        "window_s"]
    assert 0 < host_gaps.host_work_s(own["gaps"]) <= idle
    assert own["gaps"].get(trace_reduce.NO_CALL, 0.0) < red["gaps"].get(
        trace_reduce.NO_CALL, 0.0)


def test_traced_run_reports_the_phase_metrics():
    """A traced run of the tiny write cell (on the CPU: no device metric)
    reports the metrics read from the program's phases."""
    from chipbench import run

    out = run.run(tiny_cell("write50"), SEED, 2.0, True, require_chip=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["engine.host_ms_per_batch"]["value"] > 0
    assert m["write.repair_p95_ms"]["value"] > 0
    assert "device.idle_host_share" not in m
