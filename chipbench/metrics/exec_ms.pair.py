"""Mean host time of a pair batch, launch to answers on the host: the
engine's ``execute[pair]`` span, in ms."""


def read(ctx):
    d = [(e["t1"] - e["t0"]) * 1e3 for e in ctx.spans
         if e["name"] == "execute[pair]"]
    return sum(d) / len(d) if d else None
