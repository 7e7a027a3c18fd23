"""Feature plane: share of fetched rows found in the feature cache over
the window (``FeatureCache.stats`` hits and misses), in %."""


def read(ctx):
    n = ctx["hits"] + ctx["misses"]
    return 100.0 * ctx["hits"] / n if n else None
