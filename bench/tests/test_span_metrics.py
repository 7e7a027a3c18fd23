"""bench/spans.py and the readers of the program's spans: the idle
attribution on known intervals, all nine readers on a trace recorded on
the CPU from a tiny pipeline and engine, and None where there is no trace
or the trace holds no ``a3gnn.*`` span (a program that predates them)."""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spans  # noqa: E402

STEPS = 4
NODES = [3, 3, 5, 7, 11, 3, 13]      # node 3's twin stalls the first step
READERS = ["batch_wait_ms.train", "plane_lock_wait_ms.train",
           "step_sync_ms.train", "h2d_mb.train", "input_pad_share.train",
           "engine_sample_ms.serve", "engine_fetch_ms.serve",
           "engine_forward_ms.serve", "admit_stall_share.serve"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_idle_attributed_to_innermost_driving_span():
    driving = [("pipeline.wait_batch", 0, 300), ("train.feed", 300, 500),
               ("train.sync", 500, 900), ("engine.step", 1000, 1600),
               ("engine.sample", 1100, 1300)]
    ops = [(200, 250), (350, 450), (600, 700), (1150, 1200)]
    idle = spans.idle_by_span(driving, ops, 0, 2000)
    assert idle == pytest.approx({
        "pipeline.wait_batch": 250e-9, "train.feed": 100e-9,
        "train.sync": 300e-9, "engine.step": 400e-9,
        "engine.sample": 150e-9, "none": 500e-9})
    assert sum(idle.values()) == pytest.approx(2000e-9 - 300e-9)
    # a child opening with its parent is the innermost, whatever the order
    assert spans.idle_by_span([("engine.sample", 0, 50),
                               ("engine.step", 0, 100)], [], 0, 100) == \
        pytest.approx({"engine.sample": 50e-9, "engine.step": 50e-9})
    # clipped to the window
    assert spans.idle_by_span(driving, ops, 250, 550) == pytest.approx(
        {"pipeline.wait_batch": 50e-9, "train.feed": 100e-9,
         "train.sync": 50e-9})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A CPU trace: ``STEPS`` mode1 steps on the device plane, then an
    engine over ``NODES``, inside one ``bench.window`` span."""
    import jax
    from repro.configs.gnn import gnn_config
    from repro.core.a3gnn import A3GNNTrainer
    from repro.graph.synthetic import dataset_like
    from repro.serve.gnn_engine import GNNInferenceEngine, GNNRequest
    cfg = gnn_config("products", smoke=True).replace(
        parallel_mode="mode1", workers=2, sampling_device="device")
    graph = dataset_like(cfg, seed=0)
    tr = A3GNNTrainer(graph, cfg, seed=0)
    mbs = []
    train_fn = tr._train_fn

    def recording(mb, plane=None):
        mbs.append(mb)
        return train_fn(mb, plane)
    tr._train_fn = recording
    pipe = tr.make_pipeline()
    eng = GNNInferenceEngine.from_trainer(tr, batch=4, plane=pipe.plane)
    seeds = np.flatnonzero(graph.train_mask)[:STEPS * cfg.batch_size]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = tmp_path_factory.mktemp("traces")
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            pipe.submit(np.split(seeds, STEPS))
            while pipe.step():
                pass
            for rid, v in enumerate(NODES):
                eng.submit(GNNRequest(rid=rid, node=v))
            retired = []
            while eng.has_work():
                retired.append(eng.step())
    finally:
        jax.profiler.stop_trace()
        pipe.shutdown()
    return {"path": sorted(trace_dir.rglob("*.xplane.pb"))[-1], "mbs": mbs,
            "retired": retired}


def test_reduction_of_recorded_trace(recorded, capsys):
    spans._reduced.cache_clear()
    red = spans.load({"xplane": recorded["path"]})
    by = red["by_name"]
    assert {k: by[k]["n"] for k in ("pipeline.produce", "pipeline.wait_batch",
                                    "train.feed", "train.sync")} == \
        dict.fromkeys(("pipeline.produce", "pipeline.wait_batch",
                       "train.feed", "train.sync"), STEPS)
    assert by["engine.step"]["n"] == len(recorded["retired"])
    assert all(0 < v["s"] <= red["window_s"] for v in by.values())
    assert red["idle_s"] == {}               # no TPU plane on the CPU
    spans.load({"xplane": recorded["path"]})
    logged = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("[spans] ")]
    assert len(logged) == 1                  # once per trace file


def test_readers_on_recorded_trace(recorded):
    from repro.graph.batch import batch_device_arrays
    ctx = {"xplane": recorded["path"], "steps_consumed": STEPS}
    by = spans.load(ctx)["by_name"]
    got = {name: reader(name)(ctx) for name in READERS}
    assert all(v is not None for v in got.values()), got
    for name, span_name in [("batch_wait_ms.train", "pipeline.wait_batch"),
                            ("plane_lock_wait_ms.train", "plane.lock_wait"),
                            ("step_sync_ms.train", "train.sync")]:
        assert got[name] == pytest.approx(by[span_name]["s"] / STEPS * 1e3)
    n = len(recorded["retired"])
    for part in ("sample", "fetch", "forward"):
        assert got[f"engine_{part}_ms.serve"] == pytest.approx(
            by[f"engine.{part}"]["s"] / n * 1e3)
    arrays = [batch_device_arrays(mb) for mb in recorded["mbs"]]
    h2d = [a["features"].nbytes + a["labels"].nbytes
           + sum(x.nbytes for x in a["neigh_idxs"]) for a in arrays]
    assert got["h2d_mb.train"] == pytest.approx(np.mean(h2d) / 1e6)
    assert got["input_pad_share.train"] == pytest.approx(
        100 * (1 - sum(a["sizes"][0] for a in arrays)
               / sum(a["pads"][0] for a in arrays)))
    # steps: 1 admitted with 3 slots free and 6 queued; 4 admitted, none
    # free; the last 2 with nothing left queued
    assert recorded["retired"] == [1, 4, 2]
    assert got["admit_stall_share.serve"] == pytest.approx(100 / 3)


def test_readers_without_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACES", tmp_path / "none")
    ctx = {"steps_consumed": STEPS}
    assert spans.load(ctx) is None
    assert all(reader(name)(ctx) is None for name in READERS)
    # a trace with no program span: the v5e recording of bench/trace.py
    ctx["xplane"] = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
    red = spans.load(ctx)
    assert red["by_name"] == {} and red["window_s"] > 0
    assert red["idle_s"] == pytest.approx(
        {"none": red["window_s"] - 5.102e-05})
    assert all(reader(name)(ctx) is None for name in READERS)
