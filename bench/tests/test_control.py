"""The lower-precision control fails the cells' limits: the reference at
three-pass bfloat16 matmuls, put in the program's place, at a small size
on the CPU.  (On
the chip, ``run.py --calibrate`` reads the same control at the cells'
own sizes; PERF.md gives those readings.)"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import checks, common, graphgen, reference, serve, train  # noqa: E402,E501

CONFIG = json.loads((ROOT / "bench/configs/sage-products.json").read_text())
CONFIG.update(name="control-small", num_nodes=6000, num_edges=120000,
              hidden=256, batch_size=128)


def _cell(traffic: str, limits: str, **over) -> dict:
    t = json.loads((ROOT / f"bench/traffic/{traffic}.json").read_text())
    t.update(over)
    return {"config": CONFIG, "model": common.load_model(CONFIG),
            "traffic": t,
            "limits": json.loads((ROOT / f"bench/limits/{limits}.json")
                                 .read_text())}


@pytest.fixture(scope="module")
def graph():
    d = {k: CONFIG[k] for k in graphgen.DATASET_KEYS if k in CONFIG}
    seed = d.pop("dataset_seed")
    return common.make_graph(CONFIG, graphgen.build(seed=seed, threads=2,
                                                    **d))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_fails(graph, seed):
    cell = _cell("train-cached", "sage-products.train", cache_volume_mb=0.3)
    tr, pipe, rec, _ = train.setup(cell, graph, seed, common.Spans(False),
                                   print)
    train.stop(pipe)
    train.free(tr, pipe)
    ref = train.reference_readings(cell, graph, rec, seed)
    prog = checks.train_readings(train.program_readings(cell, rec), ref)
    assert checks.passed(checks.judge(dict(
        prog, rows_bad=0.0, sample_bad=0.0), cell["limits"])), prog
    ctrl = checks.train_readings(train.reference_readings(
        cell, graph, rec, seed, mode=reference.control_mode(CONFIG)), ref)
    assert not checks.passed(checks.judge(dict(
        ctrl, rows_bad=0.0, sample_bad=0.0), cell["limits"])), ctrl


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails(graph, seed, monkeypatch):
    monkeypatch.setattr(serve, "CHECK_REQUESTS", 64)
    cell = _cell("serve-zipf-over", "sage-products.serve-over",
                 cache_volume_mb=0.3, rate_qps=60.0)
    tr, eng, cap = serve.setup(cell, graph, seed, common.Spans(False))
    _, _, _, _, kept = serve._window(cell, graph, eng, cap, seed, 2.0,
                                     common.Spans(False))
    serve._free(tr, eng)
    served, ref = serve.reference_readings(cell, graph, kept, seed)
    _, ctrl = serve.reference_readings(cell, graph, kept, seed,
                                       mode=reference.control_mode(CONFIG))
    lim = cell["limits"]
    assert checks.passed(checks.judge(dict(
        checks.serve_readings(served, ref), rows_bad=0.0, sample_bad=0.0),
        lim))
    assert not checks.passed(checks.judge(dict(
        checks.serve_readings(ctrl, ref), rows_bad=0.0, sample_bad=0.0),
        lim))
