"""Device step: the consumer's wait for the step's loss and accuracy
(``a3gnn.train.sync`` around ``float(loss), float(acc)``) per consumed
step, in ms (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "train.sync", ctx.get("steps_consumed"))
