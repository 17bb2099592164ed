"""Mean host time of a read batch spent outside waiting on the device, in
ms: the read thread's ``read.form``, ``execute.dispatch``,
``execute.fetch`` and ``read.scatter`` phases, summed over the traced read
batches (one of each per batch) and divided by their number."""

PHASES = ("read.form", "execute.dispatch", "execute.fetch", "read.scatter")


def read(ctx):
    n = sum(1 for e in ctx.spans if e["name"] == "read.form")
    if not n:
        return None
    return 1e3 * sum(e["t1"] - e["t0"] for e in ctx.spans
                     if e["name"] in PHASES) / n
