"""Named host spans at the program's layer boundaries.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation`` named
``a3gnn.<name>``: while the profiler runs it lands in the same trace as
the device's operations, on the same clock, with ``counts`` (ints or
floats the caller already holds) as the event's arguments; with the
profiler off it costs well under a microsecond.  Arguments known only
inside the span go in with ``set_metadata`` on the returned object.
There is no switch of its own: starting the profiler is the switch.

Names stay clear of ``bench.``, the prefix of the benchmark's own spans.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax.profiler import TraceAnnotation

PREFIX = "a3gnn."


def span(name: str, **counts) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **counts)


def host_bytes(args) -> int:
    """Bytes of the host (numpy) arrays in ``args``: what a jitted call
    given ``args`` copies to the device."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(args)
               if isinstance(x, np.ndarray))


def level_rows(arrays: dict) -> Dict[str, int]:
    """``real<i>`` and ``pad<i>``: real and padded rows of each node level
    (input hop first) of ``graph/batch.py:batch_device_arrays``."""
    out = {f"real{i}": int(n) for i, n in enumerate(arrays["sizes"])}
    out.update({f"pad{i}": int(n) for i, n in enumerate(arrays["pads"])})
    return out
