"""Device step: host bytes handed to the jitted train step per step (the
padded feature rows, neighbour indices and labels it copies to the
chip), the mean ``h2d_bytes`` of ``a3gnn.train.feed``, in 10^6 B
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    rec = spans.entry(ctx, "train.feed")
    if not rec:
        return None
    return sum(a["h2d_bytes"] for a in rec["args"]) / rec["n"] / 1e6
