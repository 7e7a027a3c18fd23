"""Pipeline: the consumer's wait for a produced batch
(``a3gnn.pipeline.wait_batch`` around the output queue's ``get``) per
consumed step, in ms (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "pipeline.wait_batch",
                             ctx.get("steps_consumed"))
