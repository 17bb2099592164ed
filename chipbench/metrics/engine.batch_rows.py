"""Mean rows of an executed read batch: the engine's own
``mean_batch_rows`` counter over the window."""


def read(ctx):
    return ctx.stats["mean_batch_rows"] if ctx.stats["batches"] else None
