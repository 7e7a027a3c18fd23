"""Serving engine: padding, the copy to the chip, the forward and the
read-back of the logits (``a3gnn.engine.forward``) per engine step, in ms
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "engine.forward", spans.engine_steps(ctx))
