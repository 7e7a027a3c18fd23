"""Host sampler: sampling seconds per consumed batch, summed over the
pipeline's worker threads (``PipelineStats.t_sample``), in ms."""


def read(ctx):
    n = ctx.get("steps_consumed")
    return ctx["t_sample"] / n * 1e3 if n else None
