"""bench/run.py end to end on the CPU at a tiny size, past the look for a
chip: a sound run is correct, and each fault a cell can have, planted in
the timed path, makes it incorrect."""
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

from bench import run, serve, train  # noqa: E402

TINY = dict(name="tiny", num_nodes=3000, num_edges=60000, feat_dim=24,
            num_classes=7, power_exp=2.2, hidden=32, batch_size=64,
            fanout=[5, 4, 3])


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
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "train" if "train" in m["workloads"][0] else "serve"
            m["workloads"] = [f"tiny.{kind}"]
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


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.serve"])
def test_sound_run_is_correct(root, cell):
    rc, line, _ = result(root, "--workload", cell)
    assert rc == 0 and line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", "stale_state"), ("tiny.train", "half_batch"),
    ("tiny.train", "altered_rows"), ("tiny.serve", "altered_answer")])
def test_fault_is_incorrect(root, cell, fault):
    rc, line, _ = result(root, "--workload", cell, "--fault", fault)
    assert rc == 0 and not line["correct"], line["checks"]


def test_no_chip_no_result(root, capsys):
    rc = run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                   "1"], root=root)
    assert rc == 2 and capsys.readouterr().out == ""
