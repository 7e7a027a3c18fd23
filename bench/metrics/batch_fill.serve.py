"""Admission: mean requests an engine step retires (of 16 slots) over the
window.  Same-node queries wait for their twin to retire and block the
head of the queue, so this stays under the slot count."""
import numpy as np


def read(ctx):
    fill = ctx.get("fill")
    return float(np.mean(fill)) if fill else None
