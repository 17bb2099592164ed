"""Find a cell's knee: the same deployment under rising offered rates.

    python3 chipbench/sweep.py --workload ml1m.read --seed 5 --seconds 20 \\
        --rates 500,1000,2000

Builds the deployment once, then drives one window per rate and prints,
for each, the 95th percentiles, the failures and how late the generator
ran, one JSON line each. The knee is the highest rate whose read p95s stay within the
configuration's ``latency_limits_ms`` (and the write p95, where the mix
writes) with nothing shed. Runs only on the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    dep = run.prepare(c, args.seed)
    lim = c.cfg["latency_limits_ms"]
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        w = run.drive(dep, c, args.seed, args.seconds, rate, False)
        reads = [w.e2e.get(k, 0.0) for k in ("pair_p95_ms", "topn_p95_ms")]
        ok = (max(reads) <= lim["read"] and w.failed == 0
              and w.e2e.get("write_p95_ms", 0.0) <= lim["write"])
        if ok:
            knee = rate
        print(json.dumps({"rate": rate, "within_limits": ok, **w.e2e,
                          "failed": w.failed, "attempted": w.attempted,
                          "late_p99_ms": (None if w.late is None
                                          else run.quantile(w.late, 0.99) * 1e3),
                          "batches": w.stats["batches"],
                          "mean_batch_rows": w.stats["mean_batch_rows"]}),
              flush=True)
    dep.stalls.stop()
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
