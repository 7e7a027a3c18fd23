"""Device step: the consumer's train call per batch, host clock, ending in
the program's own loss read (``PipelineStats.t_train``), in ms."""


def read(ctx):
    n = ctx.get("steps_consumed")
    return ctx["t_train"] / n * 1e3 if n else None
