"""Share of the captured window in which the device sat idle while a host
thread of the program was at work, in %: 100 * (idle seconds named after a
host work span) / window, with idle stretches named by
``chipbench.host_gaps`` (the innermost ``repro/`` or ``chipbench/`` span
open at their middle). Idle spent waiting for traffic, for a batch to
fill or for the device is left out, so it is at most
``device.idle_share``. Nothing without the program's ``repro/`` spans in
the capture. The split of the idle time goes to standard error."""
import json

from chipbench import host_gaps
from chipbench.run import RUNS_DIR


def read(ctx):
    red = ctx.trace
    if not red or not red["window_s"]:
        return None
    own = host_gaps.find_capture(red["window_s"], RUNS_DIR)
    if own is None or not own["repro_spans"]:
        return None
    gaps = dict(sorted(own["gaps"].items(), key=lambda kv: -kv[1]))
    ctx.log("idle by host span: " + json.dumps(gaps))
    return 100.0 * host_gaps.host_work_s(own["gaps"]) / own["window_s"]
