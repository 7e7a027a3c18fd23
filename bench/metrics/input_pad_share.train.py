"""Device step: share of the input level's rows that are padding, over
the window's steps: 1 - sum(real0) / sum(pad0) of ``a3gnn.train.feed``,
in % (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    rec = spans.entry(ctx, "train.feed")
    if not rec:
        return None
    pad = sum(a["pad0"] for a in rec["args"])
    return 100.0 * (1.0 - sum(a["real0"] for a in rec["args"]) / pad)
