"""Mean host time of a topn batch, launch to answers on the host: the
engine's ``execute[topn]`` span, in ms."""


def read(ctx):
    d = [(e["t1"] - e["t0"]) * 1e3 for e in ctx.spans
         if e["name"] == "execute[topn]"]
    return sum(d) / len(d) if d else None
