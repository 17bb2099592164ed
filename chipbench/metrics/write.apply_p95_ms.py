"""p95 of the time the write lane spent applying one write, from pickup to
publish: the engine's ``apply[kind]`` spans of every update, fold and
remove, in ms."""
from chipbench.run import p95


def read(ctx):
    d = [(e["t1"] - e["t0"]) * 1e3 for e in ctx.spans
         if e["name"] in ("apply[update]", "apply[fold]", "apply[remove]")]
    return p95(d) if d else None
