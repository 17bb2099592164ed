"""Share of the captured window in which no operation ran on the device,
in %: 100 * (1 - busy / window) from the profiler capture."""


def read(ctx):
    red = ctx.trace
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
