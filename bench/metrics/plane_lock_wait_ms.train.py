"""Feature plane: waits for the device plane's lock
(``a3gnn.plane.lock_wait``) per consumed step, summed over the worker
threads, in ms: the same base as ``batchgen_ms.train``, whose time holds
these waits (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.per_step_ms(ctx, "plane.lock_wait",
                             ctx.get("steps_consumed"))
