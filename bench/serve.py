"""Open-loop node-query serving through the program's inference engine.

The cell offers more than the engine sustains, so its end-to-end metric
is the requests completed per second inside the window; the latency tail
and the engine's step time are per-layer metrics.

Set-up builds the trainer's cache and sampler bias as the serving entry
point does, one ``GNNInferenceEngine`` with ``slots`` slots and the
benchmark's weights for ``--seed`` (the cell's model module,
``bench/models/<model>.py``), and warms the plane's gather shapes
and the engine's one forward signature.  Arrivals are Poisson at the
fixed ``rate_qps``: the inter-arrival gaps are one fixed draw (from
``arrival_seed``) that ``--seed`` puts in another order, so every seed
offers the same load.  Query nodes are Zipf over a ``--seed`` permutation
of the test nodes.  One thread submits every due request between engine
steps.  Each request is timed from when it was due; every request due in
the window is drained and counted.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, common, reference

WARM_STEPS = 3          # full-slot engine steps in the warm-up
CHECK_REQUESTS = 256    # finished requests compared with the reference
DRAIN_SECONDS = 60.0    # how long past the window a due request may take
TRACE_SECONDS = 12      # the traced run's window


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Offsets (s) of the requests due inside the window."""
    rate = float(traffic["rate_qps"])
    n = int(rate * seconds * 1.5) + 64
    gaps = np.random.default_rng(traffic["arrival_seed"]).exponential(
        1.0 / rate, size=n)
    t = np.cumsum(np.random.default_rng(seed).permutation(gaps))
    return t[t < seconds]


def query_nodes(graph, traffic: dict, n: int, seed: int) -> np.ndarray:
    pool = np.flatnonzero(getattr(graph, f"{traffic['node_pool']}_mask"))
    rng = np.random.default_rng(seed)
    pool = rng.permutation(pool)
    p = 1.0 / np.arange(1, len(pool) + 1) ** float(traffic["zipf_exponent"])
    return pool[rng.choice(len(pool), size=n, p=p / p.sum())]


def level_caps(slots: int, fanout, num_nodes: int):
    caps = [slots]
    for f in fanout:
        caps.append(min(caps[-1] * (1 + f), num_nodes))
    return caps[::-1]


class Capture:
    """Keeps, for the steps that retire a sampled request, the sampled
    blocks and the rows the plane returned."""

    def __init__(self, engine, spans):
        self.last_mb = self.last_rows = None
        sample, fetch = engine.sampler.sample, engine.plane.fetch

        def sample_rec(seeds):
            with spans("sample"):
                self.last_mb = sample(seeds)
            return self.last_mb

        def fetch_rec(ids):
            with spans("fetch"):
                self.last_rows = fetch(ids)
            return self.last_rows
        engine.sampler.sample = sample_rec
        engine.plane.fetch = fetch_rec


def setup(cell: dict, graph, seed: int, spans, fault: str = ""):
    from repro.core.a3gnn import A3GNNTrainer
    from repro.serve.gnn_engine import GNNInferenceEngine, GNNRequest
    config, traffic = cell["config"], cell["traffic"]
    cfg = common.gnn_config(config, traffic)
    tr = A3GNNTrainer(graph, cfg, seed=seed)
    tr.params = cell["model"].init_weights(seed, config)
    eng = GNNInferenceEngine.from_trainer(tr, batch=traffic["slots"],
                                          seed=seed)
    if fault == "altered_answer":
        fwd = eng._fwd
        eng._fwd = lambda p, f, i: fwd(p, f, i).at[0].add(1.0)
    cap = Capture(eng, spans)
    pool = np.flatnonzero(graph.train_mask)
    k = 8
    while k <= 4096:
        eng.plane.fetch(pool[:k])
        k *= 2
    for j in range(WARM_STEPS):
        for s in range(traffic["slots"]):
            eng.submit(GNNRequest(rid=-1, node=int(
                pool[j * traffic["slots"] + s])))
        eng.run_to_completion()
    return tr, eng, cap


def drive(eng, cap, nodes, offsets, seconds, spans, keep_rids, drain_s,
          stalls):
    """Submit on schedule, step, drain.  Returns the requests, their due
    times, the window's start, each engine step's time and retirements,
    and the captured steps.  ``stalls`` (a ``common.StallWatch``) watches
    each engine step."""
    from repro.serve.gnn_engine import GNNRequest
    reqs = [GNNRequest(rid=i, node=int(v)) for i, v in enumerate(nodes)]
    step_s, fill, kept = [], [], []
    i, n = 0, len(reqs)
    with spans("window"):
        t0 = common.now()
        due = t0 + offsets
        end = t0 + seconds
        while True:
            t = common.now()
            while i < n and due[i] <= t:
                eng.submit(reqs[i])
                i += 1
            if eng.has_work():
                ts = common.now()
                with spans("engine_step"), stalls.watch("engine_step"):
                    retired = eng.step()
                step_s.append(common.now() - ts)
                fill.append(retired)
                done = eng.completed[-retired:] if retired else []
                if any(r.rid in keep_rids for r in done):
                    kept.append((cap.last_mb, cap.last_rows,
                                 [r for r in done if r.rid in keep_rids]))
            elif i < n:
                time.sleep(max(min(due[i] - common.now(), 1e-3), 0))
            elif t >= end:
                break
            else:
                time.sleep(max(min(end - t, 1e-3), 0))
            if t > end + drain_s:
                break
    return reqs, due, t0, (step_s, fill), kept


def reference_readings(cell, graph, kept, seed, mode: str = "f32"
                       ) -> tuple:
    """Served logits and the reference's, one row per sampled request."""
    config, traffic = cell["config"], cell["traffic"]
    model = cell["model"]
    params = model.init_weights(seed, config)
    pads = level_caps(traffic["slots"], config["fanout"], graph.num_nodes)
    served, ref = [], []
    for mb, _, reqs in kept:
        out = reference.logits(model, params,
                               graph.features[mb.blocks[0].src_ids],
                               [b.neigh_idx for b in mb.blocks], pads,
                               mode=mode)
        for r in reqs:
            row = int(np.flatnonzero(mb.seeds == r.node)[0])
            served.append(r.logits)
            ref.append(out[row])
    return np.array(served), np.array(ref)


def exact_checks(graph, kept) -> dict:
    rows = sum(checks.rows_bad(rows, graph.features[mb.input_ids])
               for mb, rows, _ in kept)
    bad = sum(checks.sample_bad(
        graph.indptr, graph.indices,
        [(b.dst_ids, b.src_ids, b.neigh_idx) for b in mb.blocks])
        for mb, _, _ in kept)
    return {"rows_bad": float(rows), "sample_bad": float(bad)}


def _window(cell, graph, eng, cap, seed, seconds, spans, log=None):
    traffic = cell["traffic"]
    offsets = arrivals(traffic, seconds, seed)
    nodes = query_nodes(graph, traffic, len(offsets), seed)
    rng = np.random.default_rng(seed)
    keep = set(rng.choice(len(offsets), size=min(CHECK_REQUESTS,
                                                 len(offsets)),
                          replace=False).tolist())
    stalls = common.StallWatch()
    try:
        return drive(eng, cap, nodes, offsets, seconds, spans, keep,
                     DRAIN_SECONDS, stalls)
    finally:
        stalls.close()
        if log:
            stalls.report(log)


def _free(tr, eng) -> None:
    import gc
    for buf in (eng.plane.__dict__.get("_dev_table"),
                eng.plane.__dict__.get("_dev_slots")):
        if buf is not None and not buf.is_deleted():
            buf.delete()
    tr.params = eng.params = None
    gc.collect()


def run(cell: dict, graph, args, compiles, log, tracing: bool,
        trace_dir) -> dict:
    spans = common.Spans(tracing)
    tr, eng, cap = setup(cell, graph, args.seed, spans, args.fault)
    seconds = min(args.seconds, TRACE_SECONDS) if tracing else args.seconds
    c0 = compiles.n
    with common.profiled(trace_dir, tracing):
        reqs, due, t0, (step_s, fill), kept = _window(
            cell, graph, eng, cap, args.seed, seconds, spans, log)
    peak = common.memory_peak_bytes()
    served = [r for r in reqs if r.status == "done"]
    in_window = sum(r.t_done <= t0 + seconds for r in served)
    lat = np.array([r.t_done - due[r.rid] for r in served]) * 1e3
    late = max((r.t_submit - due[r.rid] for r in reqs if r.t_submit),
               default=0.0)
    log(f"[window] {len(reqs)} requests due in {seconds} s, {in_window} "
        f"served inside it, {len(served)} after the drain; {len(step_s)} "
        f"engine steps; {compiles.n - c0} compiles inside the window; "
        f"generator at most {late * 1e3:.3f} ms late; latency p50 "
        f"{np.percentile(lat, 50):.1f} ms, p99 {np.percentile(lat, 99):.1f} "
        f"ms")
    ctx = {"engine_step_s": step_s, "fill": fill, "latency_ms": lat}
    _free(tr, eng)
    readings = exact_checks(graph, kept)
    readings.update(checks.serve_readings(
        *reference_readings(cell, graph, kept, args.seed)))
    return {"t_window": t0, "peak": peak, "attempted": len(reqs),
            "failed": len(reqs) - len(served),
            "end_to_end": {"serve_qps": in_window / seconds},
            "ctx": ctx, "readings": readings}


def calibrate(cell: dict, graph, args, compiles, log) -> None:
    """For each seed: a short window at the cell's load, then the
    program's readings and the lower-precision controls'."""
    import json
    spans = common.Spans(False)
    for seed in range(args.seed, args.seed + args.calibrate):
        tr, eng, cap = setup(cell, graph, seed, spans)
        _, _, _, _, kept = _window(cell, graph, eng, cap, seed,
                                   args.seconds, spans, log)
        _free(tr, eng)
        served, ref = reference_readings(cell, graph, kept, seed)
        out = {"seed": seed, "exact": exact_checks(graph, kept),
               "program": checks.serve_readings(served, ref),
               "requests": len(served)}
        for mode in ("bf16x3", "bf16"):
            _, ctrl = reference_readings(cell, graph, kept, seed, mode=mode)
            out[f"control_{mode}"] = checks.serve_readings(ctrl, ref)
        log("[calibrate] " + json.dumps(out))


def sweep(cell: dict, graph, args, compiles, log) -> None:
    """Backlog and latency at each offered rate, for finding the knee."""
    import json
    spans = common.Spans(False)
    tr, eng, cap = setup(cell, graph, args.seed, spans)
    for rate in [float(r) for r in args.sweep.split(",")]:
        traffic = dict(cell["traffic"], rate_qps=rate)
        offsets = arrivals(traffic, args.seconds, args.seed)
        nodes = query_nodes(graph, traffic, len(offsets), args.seed)
        backlog = []
        old_step = eng.step

        def step_rec():
            backlog.append((common.now(), len(eng.pending)))
            return old_step()
        eng.step = step_rec
        stalls = common.StallWatch()
        reqs, due, t0, (step_s, _), _ = drive(eng, cap, nodes, offsets,
                                              args.seconds, spans, set(),
                                              DRAIN_SECONDS, stalls)
        stalls.close()
        stalls.report(log)
        eng.step = old_step
        half = [b for t, b in backlog if t < t0 + args.seconds / 2]
        late = [b for t, b in backlog if t0 + args.seconds / 2 <= t
                < t0 + args.seconds]
        lat = np.array([r.t_done - due[r.rid] for r in reqs
                        if r.status == "done"]) * 1e3
        log("[sweep] " + json.dumps({
            "rate": rate, "requests": len(reqs),
            "backlog_first_half": float(np.mean(half)) if half else 0.0,
            "backlog_second_half": float(np.mean(late)) if late else 0.0,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "engine_step_ms": float(np.mean(step_s) * 1e3)}))
