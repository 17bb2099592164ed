"""Record the small capture that ``test_bench.py`` reduces. On the chip:

    python3 chipbench/tests/record_capture.py

drives the tiny read cell of ``tiny.py`` for 5 s with a capture of half a
second, a few hundred KB, and copies it to
``chipbench/tests/v5e_capture.xplane.pb``.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import run, trace_reduce  # noqa: E402
from chipbench.tests.tiny import tiny_cell  # noqa: E402


def main() -> int:
    c = tiny_cell("read", rate=200.0)
    run.TRACE_S = 0.5
    dep = run.prepare(c, 3)
    out = run.RUNS_DIR / "capture"
    run.drive(dep, c, 3, 5.0, 60.0, True, trace_dir=out)
    path = trace_reduce.find_xplane(str(out))
    shutil.copy(path, HERE / "v5e_capture.xplane.pb")
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
