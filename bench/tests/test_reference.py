"""bench/reference.py with bench/models/graphsage.py agrees with the
program's train step and serving forward on one sampled batch, at a small
size on the CPU."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import checks, common, graphgen, reference  # noqa: E402

CONFIG = dict(name="ref-tiny", model="graphsage", num_layers=3, hidden=32,
              feat_dim=24, num_classes=7, fanout=[5, 4, 3], batch_size=64,
              num_nodes=2000, num_edges=40000, power_exp=2.2,
              compute_dtype="float32",
              optimizer={"lr": 0.003, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                         "weight_decay": 0.0})
MODEL = common.load_model(CONFIG)
TRAFFIC = dict(bias_rate=2.0, cache_volume_mb=0.05, cache_policy="static",
               sampling_device="cpu", parallel_mode="seq", workers=1)


@pytest.fixture(scope="module")
def setup():
    from repro.core.a3gnn import A3GNNTrainer
    from repro.core.sampling import NeighborSampler
    arrays = graphgen.build(seed=0, degree_cap="sqrt_edges", threads=2,
                            **{k: CONFIG[k] for k in (
                                "num_nodes", "num_edges", "power_exp",
                                "feat_dim", "num_classes")})
    graph = common.make_graph(CONFIG, arrays)
    cfg = common.gnn_config(CONFIG, TRAFFIC)
    tr = A3GNNTrainer(graph, cfg, seed=3)
    tr.params = MODEL.init_weights(3, CONFIG)
    seeds = np.flatnonzero(graph.train_mask)[:CONFIG["batch_size"]]
    mb = NeighborSampler(graph, cfg.fanout, seed=3).sample(seeds)
    return graph, tr, mb


def test_train_step_agrees(setup):
    from repro.graph.batch import generate_batch
    graph, tr, mb = setup
    p0 = jax.tree.map(np.asarray, tr.params)
    loss, _ = tr._train_fn(generate_batch(mb, None, graph))
    batch = (graph.features[mb.input_ids], [b.neigh_idx for b in mb.blocks],
             graph.labels[mb.seeds])
    ref_losses, g0, p_end = reference.train(
        MODEL, MODEL.init_weights(3, CONFIG), [batch], CONFIG["optimizer"])
    b1 = CONFIG["optimizer"]["b1"]
    r = checks.train_readings(
        {"losses": [loss], "params0": p0,
         "params_end": jax.tree.map(np.asarray, tr.params),
         "g0": jax.tree.map(lambda m: np.asarray(m) / (1 - b1),
                            tr.opt_state["m"])},
        {"losses": ref_losses, "g0": g0, "params0": p0, "params_end": p_end})
    assert r["loss_gap"] < 1e-6, r
    assert r["grad_gap"] < 1e-5 and r["update_gap"] < 1e-5, r


def test_serving_forward_agrees(setup):
    from repro.graph.batch import generate_batch, inference_arrays
    from repro.models.gnn import gnn_forward
    graph, tr, mb = setup
    caps = [4096, 1024, 256, 64]
    arrays = inference_arrays(generate_batch(mb, None, graph),
                              level_caps=caps)
    params = MODEL.init_weights(3, CONFIG)
    got = np.asarray(gnn_forward(params, arrays["features"],
                                 arrays["neigh_idxs"], tr.cfg))
    want = reference.logits(MODEL, params, graph.features[mb.input_ids],
                            [b.neigh_idx for b in mb.blocks], caps)
    n = len(mb.seeds)
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-5, atol=1e-5)
