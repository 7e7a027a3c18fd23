"""A model's reference is found by the configuration's ``model``: the
GraphSAGE module gives the weights of the harness's first construction
bit for bit, the program's config is what the harness built before it
passed keys by field name, and a model with no module stops the run
naming the file to add."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402

CONFIGS = ["sage-products", "sage-reddit"]
TRAFFICS = ["train-cached", "serve-zipf-over"]


def _config(name):
    return common.load_json(ROOT / f"bench/configs/{name}.json")


def _frozen_weights(seed, config):
    """The harness's first construction of the weights of ``seed``:
    ``reference.init_weights(seed, dims_of(config))`` before the model
    modules."""
    dims = [config["feat_dim"]] + [config["hidden"]] * (
        config["num_layers"] - 1) + [config["num_classes"]]
    dims = tuple(zip(dims[:-1], dims[1:]))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * len(dims))
        out = []
        for i, (din, dout) in enumerate(dims):
            scale = 1.0 / np.sqrt(din)
            out.append({"w_self": jax.random.normal(keys[2 * i], (din, dout))
                        * scale,
                        "w_neigh": jax.random.normal(keys[2 * i + 1],
                                                     (din, dout)) * scale,
                        "b": jnp.zeros((dout,), jnp.float32)})
        return {"layers": out}
    return make(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("seed", [0, 3, 2147485011])
def test_graphsage_weights_are_the_frozen_construction(seed):
    config = _config("sage-products")
    got = common.load_model(config).init_weights(seed, config)
    want = _frozen_weights(seed, config)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.float32
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _frozen_gnn_config(config, traffic):
    """The harness's explicit construction before keys passed by field."""
    from repro.configs.gnn import GNNConfig
    knobs = {k: traffic[k] for k in (
        "bias_rate", "cache_volume_mb", "cache_policy", "sampling_device",
        "fused_gather_agg", "workers", "parallel_mode") if k in traffic}
    return GNNConfig(
        name=config["name"], model=config["model"],
        num_layers=config["num_layers"], hidden=config["hidden"],
        feat_dim=config["feat_dim"], num_classes=config["num_classes"],
        fanout=tuple(config["fanout"]), batch_size=config["batch_size"],
        num_nodes=config["num_nodes"], num_edges=config["num_edges"],
        power_exp=config["power_exp"], lr=config["optimizer"]["lr"],
        compute_dtype=config["compute_dtype"], **knobs)


@pytest.mark.parametrize("traffic", TRAFFICS)
@pytest.mark.parametrize("config", CONFIGS)
def test_gnn_config_is_the_explicit_one(config, traffic):
    c = _config(config)
    t = common.load_json(ROOT / f"bench/traffic/{traffic}.json")
    assert common.gnn_config(c, t) == _frozen_gnn_config(c, t)


def test_model_without_module_exits_naming_the_file(tmp_path):
    (tmp_path / "bench/configs").mkdir(parents=True)
    config = dict(_config("sage-products"), name="c", model="nosuch")
    (tmp_path / "bench/configs/c.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "bench/configs/c.json"}],
        "workloads": [{"name": "c.train", "config": "c",
                       "traffic": "train-cached", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    with pytest.raises(SystemExit, match=r"add bench/models/nosuch\.py"):
        common.load_cell("c.train", tmp_path)
