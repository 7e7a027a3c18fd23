"""Reduce the program's own spans in a traced run's trace.

The program marks its layer boundaries with ``a3gnn.<layer>.<part>``
host spans (``src/repro/core/spans.py``), each carrying that boundary's
counts as event arguments.  They land in the same ``.xplane.pb`` as the
device's operations and the benchmark's ``bench.window``, on one clock.
This module reads them back:

- every ``a3gnn.*`` event: its name, thread line, start, end, arguments;
- per name, the summed duration clipped to ``bench.window`` (the
  clipping rule of ``bench/trace.py:merge``), the count of events inside
  it and their arguments;
- the device-idle time of the window attributed to the innermost
  ``a3gnn.*`` span open on the driving thread (the line holding
  ``bench.window``), ``none`` where none is open.  This is logged as one
  ``[spans]`` line: what the thread feeding the chip was doing while the
  chip had nothing to run.

The trace is the one ``bench/run.py`` just wrote: ``ctx["xplane"]`` where
the context gives it, else the newest ``.xplane.pb`` under
``bench/.traces/`` (``run.py`` empties the cell's directory before the
run).  A trace without ``a3gnn.*`` events (a program that predates them)
reduces to empty tables, and the readers return None.
"""
from __future__ import annotations

import functools
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Optional

from bench import common
from bench.trace import WINDOW, merge

PREFIX = "a3gnn."
TRACES = Path(__file__).resolve().parent / ".traces"


def find(ctx: dict) -> Optional[Path]:
    if ctx.get("xplane"):
        return Path(ctx["xplane"])
    files = sorted(TRACES.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


def read(path) -> dict:
    """``{"spans": [(name, line, start, end, args)], "window": (lo, hi) or
    None, "driver": line of bench.window, "ops": [(start, end)]}``; ops
    are the ``XLA Ops`` events of the first TPU device, lines are
    ``(plane, index)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, window, driver, ops = [], None, None, {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns)
                               for line in plane.lines
                               if line.name == "XLA Ops"
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name == WINDOW:
                        window, driver = (e.start_ns, end), (plane.name, i)
                    elif e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], (plane.name, i),
                                      e.start_ns, end, dict(e.stats)))
    first = ops[min(ops)] if ops else []
    return {"spans": spans, "window": window, "driver": driver, "ops": first}


def idle_by_span(spans, ops, lo: float, hi: float) -> dict:
    """Seconds of ``[lo, hi]`` with no device op, by the innermost of
    ``spans`` (``(name, start, end)``, one thread's, so nested) open at
    the time; ``none`` where none is."""
    busy = merge(ops, lo, hi)
    idle, t = [], lo
    for a, b in busy:
        if a > t:
            idle.append((t, a))
        t = b
    if hi > t:
        idle.append((t, hi))
    # the innermost open span, piecewise: the span that opened last (at a
    # tie, ends before starts, the outer span opening first, closing last)
    edges = sorted([(s, 1, s - e, k) for k, (_, s, e) in enumerate(spans)]
                   + [(e, 0, e - s, k) for k, (_, s, e) in enumerate(spans)])
    out = defaultdict(float)
    stack, t, j = [], lo, 0
    for x, opening, _, k in edges + [(hi, 0, 0, None)]:
        x = min(max(x, lo), hi)
        label = spans[stack[-1]][0] if stack else "none"
        while j < len(idle) and t < x:       # idle time in [t, x)
            a, b = max(idle[j][0], t), min(idle[j][1], x)
            if b > a:
                out[label] += (b - a) * 1e-9
            if idle[j][1] <= x:
                j += 1
            else:
                break
        t = x
        if k is None:
            break
        if opening:
            stack.append(k)
        elif k in stack:
            stack.remove(k)
    return dict(out)


def reduce(trace: dict) -> dict:
    """``{"by_name": {name: {"s", "n", "args"}}, "idle_s": ..., "window_s"}``
    over ``bench.window``; empty tables when the trace has no window."""
    if trace["window"] is None:
        return {"by_name": {}, "idle_s": {}, "window_s": 0.0}
    lo, hi = trace["window"]
    by_name = {}
    for name, _, s, e, args in trace["spans"]:
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        rec = by_name.setdefault(name, {"s": 0.0, "n": 0, "args": []})
        rec["s"] += d * 1e-9
        rec["n"] += 1
        rec["args"].append(args)
    driving = [(n, s, e) for n, line, s, e, _ in trace["spans"]
               if line == trace["driver"]]
    idle = (idle_by_span(driving, trace["ops"], lo, hi) if trace["ops"]
            else {})
    return {"by_name": by_name, "idle_s": idle, "window_s": (hi - lo) * 1e-9}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> dict:
    red = reduce(read(path))
    common.log("[spans] " + json.dumps({
        "window_s": red["window_s"], "idle_s": red["idle_s"],
        "spans": {k: {"s": v["s"], "n": v["n"], "mean": mean_args(v["args"])}
                  for k, v in sorted(red["by_name"].items())}}))
    return red


def load(ctx: dict) -> Optional[dict]:
    """The reduction of the traced run's trace (read and logged once per
    trace file); None when there is no trace."""
    path = find(ctx)
    if path is None or not path.exists():
        return None
    return _reduced(str(path), path.stat().st_mtime_ns)


def mean_args(args) -> dict:
    tot, n = defaultdict(float), defaultdict(int)
    for a in args:
        for k, v in a.items():
            tot[k] += v
            n[k] += 1
    return {k: tot[k] / n[k] for k in tot}


def entry(ctx: dict, name: str) -> Optional[dict]:
    """The window's ``{"s", "n", "args"}`` for span ``name``; None with no
    trace or no such span in the window."""
    red = load(ctx)
    return red["by_name"].get(name) if red else None


def per_step_ms(ctx: dict, name: str, steps) -> Optional[float]:
    """Summed time of span ``name`` per step, in ms."""
    rec = entry(ctx, name)
    return rec["s"] / steps * 1e3 if rec and steps else None


def engine_steps(ctx: dict) -> int:
    rec = entry(ctx, "engine.step")
    return rec["n"] if rec else 0
