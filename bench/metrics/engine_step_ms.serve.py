"""Serving engine: mean host-clock time of ``GNNInferenceEngine.step``
(admit, sample, gather, forward, retire) over the window, in ms."""
import numpy as np


def read(ctx):
    s = ctx.get("engine_step_s")
    return float(np.mean(s)) * 1e3 if s else None
