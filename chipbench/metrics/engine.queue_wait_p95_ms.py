"""p95 of the time a read waited in the engine's queue: the ``queued``
span (submit to batch-former pickup) of every traced read, in ms."""
from chipbench.run import p95


def read(ctx):
    reads = {e["id"] for e in ctx.spans
             if e["name"] in ("serve[pair]", "serve[topn]")}
    waits = [(e["t1"] - e["t0"]) * 1e3 for e in ctx.spans
             if e["name"] == "queued" and e.get("parent") in reads]
    return p95(waits) if waits else None
