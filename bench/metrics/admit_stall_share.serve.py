"""Admission: share of engine steps whose admission stopped with slots
free and requests queued (a query for a node already in flight blocks
the head of the queue): ``a3gnn.engine.step`` with ``free`` > 0 and
``queued`` > 0, in % (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    rec = spans.entry(ctx, "engine.step")
    if not rec:
        return None
    stalled = sum(a["free"] > 0 and a["queued"] > 0 for a in rec["args"])
    return 100.0 * stalled / rec["n"]
