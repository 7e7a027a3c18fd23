"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The benchmark traces one window of a ``--trace 1`` run with the Python
tracer off.  Device operations are the events of the ``XLA Ops`` line of
every ``/device:TPU:<n>`` plane, named by their HLO instruction
(``fusion.5``, ``cache_gather.1``).  Host spans are the benchmark's own
``TraceAnnotation`` events, named ``bench.<layer>``; the window is the
``bench.window`` span.  Times are nanoseconds on the trace's clock.

- busy: the union of the device-op intervals inside the window, averaged
  over the devices;
- idle share: ``1 - busy / window``;
- kernel time: summed durations of the device ops named after the kernel
  (``cache_gather``, ``cache_gather.3``, ...);
- idle gaps: the stretches of the window with no device op, each named by
  the host span that covers most of it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


def latest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(text: str) -> str:
    """``cache_gather.1`` from the HLO text a TPU trace names its ops by
    (``%cache_gather.1 = f32[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(path) -> dict:
    """``{"ops": {device: [(name, start, end)]}, "spans": [(name, start,
    end)]}`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"ops": ops, "spans": spans}


def merge(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` clipped to ``[lo, hi]``."""
    out: List[list] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(intervals: List[Interval], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(intervals, lo, hi))


def kernel_ns(events, kernel: str, lo: float, hi: float) -> float:
    pat = re.compile(rf"{re.escape(kernel)}(\.\d+)?")
    return sum(min(e, hi) - max(s, lo) for name, s, e in events
               if pat.fullmatch(name) and min(e, hi) > max(s, lo))


def top_ops(events, lo: float, hi: float, k: int = 10):
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in events:
        if min(e, hi) > max(s, lo):
            tot[name] += min(e, hi) - max(s, lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def idle_gaps(events, spans, lo: float, hi: float, k: int = 10):
    busy = merge([(s, e) for _, s, e in events], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for a, b in gaps:
        cover: Dict[str, float] = defaultdict(float)
        for name, s, e in spans:
            if name != WINDOW and min(e, b) > max(s, a):
                cover[name[len(SPAN_PREFIX):]] += min(e, b) - max(s, a)
        label = max(cover, key=cover.get) if cover else "no span"
        out.append([label, (b - a) * 1e-9])
    return out


def summarize(trace: dict, kernels=()) -> dict:
    """Window, busy time (mean over devices), kernel seconds and the
    breakdown of one traced window; None when the trace holds no TPU
    plane (a CPU run measures no device)."""
    win = [(s, e) for name, s, e in trace["spans"] if name == WINDOW]
    if not win:
        raise ValueError("trace has no bench.window span")
    if not trace["ops"]:
        return None
    lo, hi = win[-1]
    devices = sorted(trace["ops"])
    first = trace["ops"][devices[0]]
    busy = [busy_ns([(s, e) for _, s, e in trace["ops"][d]], lo, hi)
            for d in devices]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "kernel_s": {k: sum(kernel_ns(trace["ops"][d], k, lo, hi)
                            for d in devices) / len(devices) * 1e-9
                     for k in kernels},
        "breakdown": {"device_ops": top_ops(first, lo, hi),
                      "idle_gaps": idle_gaps(first, trace["spans"], lo, hi)},
    }
