"""Kernels: the Pallas ``cache_gather``'s share of its roofline.  The
bytes the gathered rows need (``bench/counts.py:gather_bytes``: rows from
the plane's ``gather_rows``, hits from ``FeatureCache.stats``) at peak
HBM bandwidth, over the summed device time of the ``cache_gather`` ops
in the trace, in %."""
from bench.counts import gather_bytes

KERNELS = ("cache_gather",)


def read(ctx):
    tr = ctx.get("trace")
    t = tr["kernel_s"].get("cache_gather", 0.0) if tr else 0.0
    if t <= 0 or not ctx["gather_rows"]:
        return None
    need = gather_bytes(ctx["gather_rows"], ctx["hits"], ctx["feat_dim"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / t
