"""bench/run.py end to end on the CPU at a tiny size, past the look for a
chip: a sound run is correct, and each fault a cell can have, planted in
the timed path, makes it incorrect.  The tmp checkout also holds a second
model family added by new files alone: a plain GCN reference
(``bench/models/gcn.py``), its configuration, cell, limits and a reader
of one of its counts."""
import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import peaks, run, serve, train  # noqa: E402
from bench import trace as tracing  # noqa: E402

TINY = dict(name="tiny", num_nodes=3000, num_edges=60000, feat_dim=24,
            num_classes=7, power_exp=2.2, hidden=32, batch_size=64,
            fanout=[5, 4, 3])

# a GCN layer as models/gnn.py:_gcn_combine computes it: the mean of the
# sampled neighbours and the node itself, ``(mean * cnt + h) / (cnt + 1)``
# with ``cnt`` at least 1, then ``z @ w + b``
GCN = '''"""Plain GCN (sampled mean with a self loop) for the harness's tests."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dot


def dims(config):
    d = [config["feat_dim"]] + [config["hidden"]] * (
        config["num_layers"] - 1) + [config["num_classes"]]
    return list(zip(d[:-1], d[1:]))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_weights(key, dims):
    keys = jax.random.split(key, len(dims))
    return {"layers": [
        {"w": jax.random.normal(k, (din, dout)) / np.sqrt(din),
         "b": jnp.zeros((dout,), jnp.float32)}
        for k, (din, dout) in zip(keys, dims)]}


def init_weights(seed, config):
    return _make_weights(jax.random.PRNGKey(seed),
                         tuple(tuple(d) for d in dims(config)))


def forward(params, feats, idxs, mode="f32"):
    dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    h = feats.astype(dtype)
    layers = params["layers"]
    for i, (p, idx) in enumerate(zip(layers, idxs)):
        mask = idx >= 0
        nb = jnp.where(mask[..., None], h[jnp.maximum(idx, 0)], 0)
        cnt = jnp.maximum(mask.sum(1, keepdims=True), 1).astype(dtype)
        z = (nb.sum(1) / cnt * cnt + h[:idx.shape[0]]) / (cnt + 1)
        out = dot(z, p["w"].astype(dtype), mode) + p["b"].astype(dtype)
        h = jax.nn.relu(out) if i < len(layers) - 1 else out
    return h.astype(jnp.float32)


def train_counts(sizes, fanouts, config):
    flops = adds = 0
    for i, ((din, dout), f) in enumerate(zip(dims(config), fanouts)):
        n = int(sizes[i + 1])
        adds += n * f * din
        flops += n * f * din + 2 * (2 * n * din * dout)
        if i:
            flops += 2 * n * din * dout + n * f * din
    return {"flops": flops, "agg_adds": adds}
'''
READER = '''KERNELS = ("gcn_agg",)


def read(ctx):
    return ctx.get("agg_adds")
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark with one tiny configuration, run
    under the real cells' traffic mixes and limits."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".jax_cache",
                                                  ".traces", "__pycache__"))
    config = json.loads((ROOT / "bench/configs/sage-products.json")
                        .read_text())
    config.update(TINY)
    (r / "bench/configs/tiny.json").write_text(json.dumps(config))
    for kind, real in (("train", "sage-products.train"),
                       ("serve", "sage-products.serve-over")):
        shutil.copy(ROOT / f"bench/limits/{real}.json",
                    r / f"bench/limits/tiny.{kind}.json")
    tiny_train = json.loads((r / "bench/traffic/train-cached.json")
                            .read_text())
    tiny_train.update(cache_volume_mb=0.05)
    (r / "bench/traffic/tiny-train.json").write_text(json.dumps(tiny_train))
    tiny_serve = json.loads((r / "bench/traffic/serve-zipf-over.json")
                            .read_text())
    tiny_serve.update(cache_volume_mb=0.05, rate_qps=40.0)
    (r / "bench/traffic/tiny-serve.json").write_text(json.dumps(tiny_serve))
    # the second family, by new files only: one twin serves both configs
    (r / "bench/models/gcn.py").write_text(GCN)
    (r / "bench/configs/tiny-gcn.json").write_text(json.dumps(
        dict(config, name="tiny-gcn", model="gcn")))
    shutil.copy(ROOT / "bench/limits/sage-products.train.json",
                r / "bench/limits/tiny-gcn.train.json")
    (r / "bench/metrics/agg_adds.train.py").write_text(READER)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name=name,
                            file=f"bench/configs/{name}.json")
                       for name in ("tiny", "tiny-gcn")]
    spec["workloads"] = [
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "test"},
        {"name": "tiny-gcn.train", "config": "tiny-gcn",
         "traffic": "tiny-train", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.train", "tiny-gcn.train"]
                              if "train" in m["workloads"][0]
                              else ["tiny.serve"])
    spec["per_layer"].append(
        {"name": "agg_adds.train", "unit": "adds", "better": "higher",
         "source": "program_counter", "layer": "device step",
         "moves": "train_seeds_per_s", "workloads": ["tiny-gcn.train"]})
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


@pytest.fixture(autouse=True)
def small_mechanics(monkeypatch):
    """A short warm-up and a small checked sample at the tiny size."""
    monkeypatch.setattr(train, "MAX_WARM_STEPS", 4)
    monkeypatch.setattr(serve, "CHECK_REQUESTS", 16)


def result(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--seed", "5", "--seconds", "2", *argv], root=root,
                      require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.serve",
                                  "tiny-gcn.train"])
def test_sound_run_is_correct(root, cell):
    rc, line, _ = result(root, "--workload", cell)
    assert rc == 0 and line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", "stale_state"), ("tiny.train", "half_batch"),
    ("tiny.train", "altered_rows"), ("tiny.serve", "altered_answer"),
    ("tiny-gcn.train", "half_batch")])
def test_fault_is_incorrect(root, cell, fault):
    rc, line, _ = result(root, "--workload", cell, "--fault", fault)
    assert rc == 0 and not line["correct"], line["checks"]


def test_new_family_reader_reads_its_counts(root, monkeypatch):
    """A traced run of the GCN cell: the trace reduction is asked for the
    kernels of the cell's readers (the union of their ``KERNELS``), and
    the new reader reports the module's own count."""
    import jax
    asked = []
    summarize = tracing.summarize

    def spy(trace, kernels=()):
        asked.append(kernels)
        return summarize(trace, kernels)
    monkeypatch.setattr(tracing, "summarize", spy)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    rc, line, _ = result(root, "--workload", "tiny-gcn.train", "--trace",
                         "1")
    assert rc == 0 and line["correct"], line["checks"]
    assert asked == [("cache_gather", "gcn_agg")]
    assert line["metrics"]["agg_adds.train"]["value"] > 0


def test_no_chip_no_result(root, capsys):
    rc = run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                   "1"], root=root)
    assert rc == 2 and capsys.readouterr().out == ""
