"""Serving tail: 99th percentile of every request's latency from its due
time to its retirement, in ms.  Above capacity the queue grows all
through the window, so this swings with small changes: recorded, not
bounded."""
import numpy as np


def read(ctx):
    lat = ctx.get("latency_ms")
    return float(np.percentile(lat, 99)) if lat is not None and len(lat) \
        else None
