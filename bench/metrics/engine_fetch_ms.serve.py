"""Feature plane: the engine's feature fetch through the plane
(``a3gnn.engine.fetch`` around ``generate_batch``) per engine step, in ms
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "engine.fetch", spans.engine_steps(ctx))
