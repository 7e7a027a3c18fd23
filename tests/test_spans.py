"""The program's own spans (core/spans.py): a profiled mode1 pipeline on
the device plane and a profiled serving engine write every ``a3gnn.*``
span with the counts of its boundary, read back from the trace."""
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.a3gnn import A3GNNTrainer
from repro.graph.batch import batch_device_arrays
from repro.serve.gnn_engine import GNNInferenceEngine, GNNRequest

STEPS = 6


def _read(trace_dir) -> dict:
    """``{name: [(thread line, start, args)]}`` of the ``a3gnn.*`` events,
    in start order."""
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("a3gnn."):
                    out[e.name[len("a3gnn."):]].append(
                        ((plane.name, i), e.start_ns, dict(e.stats)))
    return {k: sorted(v, key=lambda x: x[1]) for k, v in out.items()}


@pytest.fixture(scope="module")
def traced(smoke_graph, smoke_gnn_cfg, tmp_path_factory):
    """One profiled run: ``STEPS`` mode1 steps through the device plane,
    then an engine sharing that plane, with a same-node twin queued."""
    cfg = smoke_gnn_cfg.replace(parallel_mode="mode1", workers=2,
                                sampling_device="device")
    tr = A3GNNTrainer(smoke_graph, cfg, seed=0)
    consumed = []
    train_fn = tr._train_fn

    def recording(mb, plane=None):
        consumed.append(mb)
        return train_fn(mb, plane)
    tr._train_fn = recording
    pipe = tr.make_pipeline()
    seeds = np.flatnonzero(smoke_graph.train_mask)[:STEPS * cfg.batch_size]
    eng = GNNInferenceEngine.from_trainer(tr, batch=4, plane=pipe.plane)
    nodes = [3, 3, 5, 7, 11, 3, 13]
    retired = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        pipe.submit(np.split(seeds, STEPS))
        while pipe.step():
            pass
        for rid, v in enumerate(nodes):
            eng.submit(GNNRequest(rid=rid, node=v))
        while eng.has_work():
            retired.append(eng.step())
    finally:
        jax.profiler.stop_trace()
        pipe.shutdown()
    return {"spans": _read(trace_dir), "consumed": consumed,
            "retired": retired, "workers": cfg.workers}


def test_every_span_with_its_arguments(traced):
    sp = traced["spans"]
    want = {"pipeline.produce": {"batch", "worker"},
            "plane.lock_wait": set(),
            "pipeline.wait_batch": {"queued", "batch"},
            "train.feed": {"h2d_bytes", "real0", "pad0", "real2", "pad2"},
            "train.sync": set(),
            "engine.step": {"step", "admitted", "active", "free", "queued"},
            "engine.sample": {"seeds"},
            "engine.fetch": {"rows"},
            "engine.forward": {"h2d_bytes"},
            "sampler.hop": {"rows", "reject_rows", "proposals", "picks"}}
    assert set(sp) == set(want)
    for name, keys in want.items():
        assert all(keys <= set(args) for _, _, args in sp[name]), name
    for name in ("pipeline.produce", "pipeline.wait_batch", "train.feed",
                 "train.sync"):
        assert len(sp[name]) == STEPS, name
    # one per hop of every sampled batch, the engine's included
    hops = len(traced["consumed"][0].blocks)
    assert len(sp["sampler.hop"]) == hops * (STEPS + len(sp["engine.sample"]))


def test_consumer_batches_match_produced_ones(traced):
    sp = traced["spans"]
    produced = {a["batch"]: a["worker"] for _, _, a in sp["pipeline.produce"]}
    consumed = [a["batch"] for _, _, a in sp["pipeline.wait_batch"]]
    assert sorted(consumed) == sorted(produced) == list(range(STEPS))
    assert set(produced.values()) <= set(range(traced["workers"]))
    # the consumer's spans share one thread; the producers run elsewhere
    consumer = {line for n in ("pipeline.wait_batch", "train.feed",
                               "train.sync") for line, _, _ in sp[n]}
    assert len(consumer) == 1
    assert consumer.isdisjoint(line for line, _, _ in sp["pipeline.produce"])
    # the plane lock is taken by the producers' fetches
    assert len(sp["plane.lock_wait"]) >= STEPS


def test_feed_counts_match_batch_arrays(traced):
    feeds = [a for _, _, a in traced["spans"]["train.feed"]]
    assert len(feeds) == len(traced["consumed"]) == STEPS
    for mb, args in zip(traced["consumed"], feeds):
        arrays = batch_device_arrays(mb)
        for i, (real, pad) in enumerate(zip(arrays["sizes"],
                                            arrays["pads"])):
            assert (args[f"real{i}"], args[f"pad{i}"]) == (real, pad)
        host = [arrays["features"], *arrays["neigh_idxs"], arrays["labels"]]
        assert args["h2d_bytes"] == sum(x.nbytes for x in host)


def test_engine_counts(traced):
    sp = traced["spans"]
    steps = [a for _, _, a in sp["engine.step"]]
    assert [a["active"] for a in steps] == traced["retired"]
    assert [a["step"] for a in steps] == list(range(len(steps)))
    assert sum(a["admitted"] for a in steps) == 7
    # node 3's twin stops admission with three slots free
    assert (steps[0]["admitted"], steps[0]["free"], steps[0]["queued"]) \
        == (1, 3, 6)
    assert [a["seeds"] for _, _, a in sp["engine.sample"]] == \
        traced["retired"]
    assert len(sp["engine.fetch"]) == len(sp["engine.forward"]) == len(steps)
    assert all(a["rows"] > 0 for _, _, a in sp["engine.fetch"])
    assert len({a["h2d_bytes"] for _, _, a in sp["engine.forward"]}) == 1
