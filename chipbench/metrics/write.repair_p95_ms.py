"""p95 of the time a write spent draining its neighbour-list repairs, in
ms: the ``repair.drain`` span that every update and removal records
inside its ``apply[kind]`` (a fold drains nothing)."""
from chipbench.run import p95


def read(ctx):
    d = [(e["t1"] - e["t0"]) * 1e3 for e in ctx.spans
         if e["name"] == "repair.drain"]
    return p95(d) if d else None
