"""Closed-loop training through the program's own trainer and pipeline.

Set-up builds one ``A3GNNTrainer`` with the benchmark's weights for
``--seed`` and one ``Pipeline`` around it, warms the plane's gather
shapes, drives the pipeline's first ``CHECK_STEPS`` batches through the
same ``submit``/``step`` call the window uses (those are the checked
steps), and keeps stepping until no step compiles and the queue has
emptied once.  The window then trains for ``--seconds``, keeping
``inflight`` seed batches submitted.  After it the pipeline stops, the
program's state is freed, and the checked steps are replayed by
``bench/reference.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools

import numpy as np

from bench import checks, common, counts, reference

CHECK_STEPS = 3        # steps the reference replays
SETTLE_STEPS = 3       # quiet steps in a row that end the warm-up
MAX_WARM_STEPS = 16    # the warm-up's limit
KEEP_BATCHES = 2       # window batches whose rows are checked ...
KEEP_RANGE = 4         # ... drawn from its first KEEP_RANGE steps
TRACE_SECONDS = 12     # the traced run's window
STALL_S = 5.0          # a wait for a step this long is logged
PAD_MARGIN = 1.05      # warm the pads of level sizes this far from those seen


def seed_batches(train_mask: np.ndarray, batch: int, seed: int):
    """Shuffled training seeds, epoch after epoch."""
    ids = np.flatnonzero(train_mask)
    rng = np.random.default_rng(seed)
    while True:
        perm = rng.permutation(ids)
        for a in range(0, len(perm) - batch + 1, batch):
            yield perm[a:a + batch].astype(np.int64)


def _host(tree):
    import jax
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def _half(mb):
    """The batch with its second half of seeds left out."""
    h = len(mb.seeds) // 2
    last = mb.blocks[-1]
    blocks = mb.blocks[:-1] + [dataclasses.replace(
        last, dst_ids=last.dst_ids[:h], neigh_idx=last.neigh_idx[:h])]
    return dataclasses.replace(mb, blocks=blocks, seeds=mb.seeds[:h],
                               labels=mb.labels[:h])


class StepRecorder:
    """Stands in the trainer's ``_train_fn`` slot and calls the original:
    records what the checked steps need, each step's real level sizes,
    and the batches kept for the row check.  ``fault`` plants a fault
    for the harness's own tests."""

    def __init__(self, tr, check_steps: int, keep_steps, spans,
                 fault: str = ""):
        self.tr, self.fn = tr, tr._train_fn
        self.check_steps = check_steps
        self.keep_steps = set(keep_steps)
        self.spans, self.fault = spans, fault
        self.count = 0
        self.window_start = None        # step count when the window opened
        self.checked, self.kept, self.losses, self.sizes = [], [], [], []
        self.params0 = self.m1 = self.params_end = None

    def __call__(self, mb, plane=None):
        tr, i = self.tr, self.count
        if i == 0:
            self.params0 = _host(tr.params)
        if self.fault == "altered_rows" and mb.features is not None:
            mb = dataclasses.replace(mb, features=mb.features + 1.0)
        before = (tr.params, tr.opt_state)
        with self.spans("step"):
            loss, acc = self.fn(_half(mb) if self.fault == "half_batch"
                                else mb, plane)
        if self.fault == "stale_state":
            tr.params, tr.opt_state = before
        self.count += 1
        self.sizes.append([len(mb.blocks[0].src_ids)]
                          + [len(b.dst_ids) for b in mb.blocks])
        if i < self.check_steps:
            self.checked.append(mb)
            self.losses.append(float(loss))
            if i == 0:
                self.m1 = _host(tr.opt_state["m"])
            if i == self.check_steps - 1:
                self.params_end = _host(tr.params)
        elif (self.window_start is not None
              and i - self.window_start in self.keep_steps):
            self.kept.append(mb)
        return loss, acc


def _warm_gather(plane, ids: np.ndarray) -> None:
    """Every gather chunk shape the plane can dispatch (pow2 tails)."""
    k = 8
    while k <= 4096:
        plane.fetch(ids[:k])
        k *= 2


def _warm_pads(tr, sizes, fanouts, feat_dim, log) -> int:
    """Compile the step for each pad a level came within ``PAD_MARGIN``
    of: the pow2 pads of the sizes seen and of sizes that far either
    side, each held to the most that level can hold.  The margin is
    narrow enough that every seed warms the same pads (each warmed pad
    adds to the device's peak), and wide enough that no window batch
    has reached another."""
    import jax
    seen = np.array(sizes)
    batch = int(seen[0, -1])
    caps = [batch]                    # the most a level can hold
    for f in reversed(fanouts):
        caps.append(caps[-1] * (1 + f))
    caps = caps[::-1]
    opts = []
    for lvl in range(seen.shape[1] - 1):
        lo, hi = seen[:, lvl].min(), seen[:, lvl].max()
        opts.append(sorted({reference.pow2(min(s, caps[lvl])) for s in
                            (int(lo / PAD_MARGIN), lo, hi,
                             int(hi * PAD_MARGIN))}))
    done = 0
    for pads in itertools.product(*opts):
        feats = np.zeros((pads[0], feat_dim), np.float32)
        idxs = [-np.ones((p, f), np.int32)
                for p, f in zip(list(pads[1:]) + [batch], fanouts)]
        labels = np.zeros(batch, np.int32)
        out = tr._step(tr.params, tr.opt_state, feats, idxs, labels)
        jax.block_until_ready(out)
        done += 1
    log(f"[warm] step pads per level {opts}: {done} compiled or found")
    return done


def setup(cell: dict, graph, seed: int, spans, log, fault: str = ""):
    """Trainer, pipeline and recorder, driven through the checked steps."""
    from repro.core.a3gnn import A3GNNTrainer
    config, traffic = cell["config"], cell["traffic"]
    cfg = common.gnn_config(config, traffic)
    tr = A3GNNTrainer(graph, cfg, seed=seed)
    tr.params = reference.init_weights(seed, common.dims_of(config))
    rng = np.random.default_rng(seed)
    keep = rng.choice(KEEP_RANGE, size=KEEP_BATCHES, replace=False)
    rec = StepRecorder(tr, CHECK_STEPS, keep, spans, fault)
    tr._train_fn = rec
    pipe = tr.make_pipeline()
    if traffic["sampling_device"] == "device":
        pipe.plane.fetch = spans.wrap(pipe.plane.fetch, "fetch")
    _warm_gather(pipe.plane, np.flatnonzero(graph.train_mask))
    feed = seed_batches(graph.train_mask, config["batch_size"], seed)
    inflight = traffic["inflight"]

    def top_up():
        while pipe.inflight < inflight:
            pipe.submit([next(feed)])

    top_up()
    for _ in range(CHECK_STEPS):
        pipe.step()
        top_up()
    return tr, pipe, rec, top_up


def settle(cell, tr, pipe, rec, top_up, compiles, log) -> None:
    """Compile the step for every pad within reach of the sizes seen,
    then step until ``SETTLE_STEPS`` steps in a row compiled nothing and
    the last one found the pipeline's queue empty (the state a
    producer-bound loop runs in), or ``MAX_WARM_STEPS`` steps."""
    config = cell["config"]
    fanouts = list(reversed(config["fanout"]))
    _warm_pads(tr, rec.sizes, fanouts, config["feat_dim"], log)
    quiet, n, empty = 0, 0, False
    while n < MAX_WARM_STEPS:
        c0 = compiles.n
        empty = pipe._out_q.qsize() == 0
        pipe.step()
        top_up()
        n += 1
        quiet = quiet + 1 if compiles.n == c0 else 0
        if quiet >= SETTLE_STEPS and empty:
            break
    log(f"[warm] {rec.count} steps before the window; last found the "
        f"queue empty: {empty}; level sizes seen {rec.sizes}")


def window(pipe, rec, top_up, seconds: float, spans, log):
    """Train from ``t0`` until the first step that finishes ``seconds``
    or more after it; returns every step's completion time, and ``t0``.
    Waits of ``STALL_S`` or more for a step are logged with the
    collector's time (``common.StallWatch``)."""
    rec.window_start = rec.count
    done = []
    stalls = common.StallWatch(stall_s=STALL_S)
    try:
        with spans("window"):
            t0 = common.now()
            end = t0 + seconds
            while True:
                with stalls.watch("pipeline_step"):
                    pipe.step()
                done.append(common.now())
                if done[-1] >= end:
                    break
                top_up()
    finally:
        stalls.close()
        stalls.report(log)
    return done, t0


def seeds_per_s(done, t0: float, batch: int) -> float:
    """Seeds per second over the whole window: every step finished since
    ``t0``, over the time from ``t0`` to the last of them.  The window
    ends on a step's completion, so the rate is not rounded to whole
    steps, and a stall anywhere in it lowers the rate."""
    return len(done) * batch / (done[-1] - t0)


def stop(pipe) -> None:
    workers = list(pipe._workers)
    pipe.shutdown()
    for w in workers:
        w.join()


def reference_readings(cell, graph, rec, seed: int, mode: str = "f32",
                       half: bool = False) -> dict:
    """The reference over the checked batches, as the program's numbers
    are: its losses, first gradient, start and end parameters."""
    config = cell["config"]
    batches = []
    for mb in rec.checked:
        n = len(mb.seeds) // 2 if half else len(mb.seeds)
        neigh = [b.neigh_idx for b in mb.blocks]
        neigh[-1] = neigh[-1][:n]
        batches.append((graph.features[mb.blocks[0].src_ids], neigh,
                        graph.labels[mb.seeds[:n]]))
    params0 = reference.init_weights(seed, common.dims_of(config))
    losses, g0, params_end = reference.train(
        params0, batches, config["optimizer"], mode=mode)
    return {"losses": losses, "g0": g0, "params0": _host(params0),
            "params_end": params_end}


def program_readings(cell, rec) -> dict:
    b1 = cell["config"]["optimizer"]["b1"]
    import jax
    return {"losses": rec.losses,
            "g0": jax.tree.map(lambda m: m / (1 - b1), rec.m1),
            "params0": rec.params0, "params_end": rec.params_end}


def exact_checks(graph, rec) -> dict:
    rows = sum(checks.rows_bad(mb.features, graph.features[mb.input_ids])
               for mb in rec.checked + rec.kept)
    bad = sum(checks.sample_bad(
        graph.indptr, graph.indices,
        [(b.dst_ids, b.src_ids, b.neigh_idx) for b in mb.blocks])
        for mb in rec.checked)
    return {"rows_bad": float(rows), "sample_bad": float(bad)}


def free(tr, pipe) -> None:
    """Drop the program's device state before the reference runs."""
    for buf in (pipe.plane.__dict__.get("_dev_table"),
                pipe.plane.__dict__.get("_dev_slots")):
        if buf is not None and not buf.is_deleted():
            buf.delete()
    tr.params = tr.opt_state = None
    gc.collect()


def run(cell: dict, graph, args, compiles, log, tracing: bool,
        trace_dir) -> dict:
    config = cell["config"]
    spans = common.Spans(tracing)
    if tracing:
        from repro.core.sampling import NeighborSampler
        NeighborSampler.sample = spans.wrap(NeighborSampler.sample, "sample")
    tr, pipe, rec, top_up = setup(cell, graph, args.seed, spans, log,
                                  args.fault)
    log(f"[check] {CHECK_STEPS} checked steps: losses {rec.losses}")
    settle(cell, tr, pipe, rec, top_up, compiles, log)
    seconds = min(args.seconds, TRACE_SECONDS) if tracing else args.seconds
    st0 = dataclasses.replace(pipe.stats)
    cache0 = dataclasses.replace(pipe.plane.cache.stats)
    rows0, c0, reissued0 = pipe.plane.gather_rows, compiles.n, \
        pipe.stats.reissued
    with common.profiled(trace_dir, tracing):
        times, t0 = window(pipe, rec, top_up, seconds, spans, log)
    done = len(times)
    st, cache = pipe.stats, pipe.plane.cache.stats
    in_window = compiles.n - c0
    stop(pipe)
    peak = common.memory_peak_bytes()
    n_win = st.steps - st0.steps
    log(f"[window] {done} steps finished in {times[-1] - t0:.3f} s "
        f"({n_win} consumed); "
        f"{in_window} compiles inside the window; reissued "
        f"{st.reissued - reissued0}")
    fanouts = list(reversed(config["fanout"]))
    dims = common.dims_of(config)
    start = rec.window_start
    flops = sum(counts.sage_train_flops(s, fanouts, dims)
                for s in rec.sizes[start:start + n_win])
    ctx = {"t_sample": st.t_sample - st0.t_sample,
           "t_batch": st.t_batch - st0.t_batch,
           "t_train": st.t_train - st0.t_train, "steps_consumed": n_win,
           "hits": cache.hits - cache0.hits,
           "misses": cache.misses - cache0.misses,
           "gather_rows": pipe.plane.gather_rows - rows0,
           "feat_dim": config["feat_dim"], "flops": flops}
    free(tr, pipe)
    readings = exact_checks(graph, rec)
    ref = reference_readings(cell, graph, rec, args.seed)
    readings.update(checks.train_readings(program_readings(cell, rec), ref))
    return {"t_window": t0, "peak": peak, "attempted": done,
            "failed": int(st.reissued - reissued0),
            "end_to_end": {
                "train_seeds_per_s": seeds_per_s(times, t0,
                                                 config["batch_size"]),
                "peak_hbm_gib": peak / 2**30},
            "ctx": ctx, "readings": readings}


def calibrate(cell: dict, graph, args, compiles, log) -> None:
    """For each seed: the checked steps, then the program's readings, the
    lower-precision controls' (``bf16x3`` and ``bf16``) and the
    half-batch fault's (planted in the reference put in the program's
    place), one JSON line each."""
    import json
    spans = common.Spans(False)
    for seed in range(args.seed, args.seed + args.calibrate):
        tr, pipe, rec, _ = setup(cell, graph, seed, spans, log)
        stop(pipe)
        free(tr, pipe)
        out = {"seed": seed, "exact": exact_checks(graph, rec)}
        ref = reference_readings(cell, graph, rec, seed)
        out["program"] = checks.train_readings(program_readings(cell, rec),
                                               ref)
        for mode in ("bf16x3", "bf16"):
            ctrl = reference_readings(cell, graph, rec, seed, mode=mode)
            out[f"control_{mode}"] = checks.train_readings(ctrl, ref)
        half = reference_readings(cell, graph, rec, seed, half=True)
        out["half_batch"] = checks.train_readings(half, ref)
        log("[calibrate] " + json.dumps(out))
