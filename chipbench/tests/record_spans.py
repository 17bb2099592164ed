"""Record a small capture that holds the program's phases. On the chip:

    python3 chipbench/tests/record_spans.py v5e_spans.xplane.pb

drives the tiny write-heavy cell of ``tiny.py`` (reads and writes, so the
read thread's and the write lane's ``repro/`` phases both show) for 5 s
with a capture of a quarter second, a few hundred KB, and copies it to
``chipbench/tests/<name>``.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import run, trace_reduce  # noqa: E402
from chipbench.tests.tiny import tiny_cell  # noqa: E402


def main(name: str) -> int:
    c = tiny_cell("write50")
    run.TRACE_S = 0.25
    dep = run.prepare(c, 3)
    out = run.RUNS_DIR / "capture"
    run.drive(dep, c, 3, 5.0, 60.0, True, trace_dir=out)
    path = trace_reduce.find_xplane(str(out))
    shutil.copy(path, HERE / name)
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
