"""Serving engine: sampling the admitted seeds' hops
(``a3gnn.engine.sample``) per engine step, in ms (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "engine.sample", spans.engine_steps(ctx))
