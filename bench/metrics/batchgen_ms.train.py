"""Feature plane: batch-generation (fetch) seconds per consumed batch,
summed over the worker threads (``PipelineStats.t_batch``), in ms."""


def read(ctx):
    n = ctx.get("steps_consumed")
    return ctx["t_batch"] / n * 1e3 if n else None
