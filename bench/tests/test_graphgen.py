"""bench/graphgen.py: the uncapped twin is powerlaw_graph's, the cap holds,
and the cache follows its key."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import graphgen  # noqa: E402

SMALL = dict(num_nodes=3000, num_edges=60000, power_exp=1.9, feat_dim=24,
             num_classes=7)


@pytest.mark.parametrize("seed", [0, 3])
def test_uncapped_equals_powerlaw_graph(seed):
    from repro.graph.synthetic import powerlaw_graph
    want = powerlaw_graph(seed=seed, **SMALL)
    got = graphgen.build(seed=seed, degree_cap=None, threads=3,
                         feat_chunk_rows=700, **SMALL)
    for k in graphgen.ARRAYS:
        a, b = got[k], getattr(want, k)
        assert a.dtype == b.dtype and np.array_equal(a, b), k


def test_cap_holds_and_keeps_entries():
    n, m = SMALL["num_nodes"], SMALL["num_edges"]
    rng = np.random.default_rng(0)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1 / 0.9)
    rng.shuffle(w)
    p = graphgen.capped_probs(w / w.sum(), m, np.sqrt(m))
    assert (m * p).max() <= np.sqrt(m) * (1 + 1e-9)
    assert abs(p.sum() - 1) < 1e-12
    before = m * w / w.sum()
    assert (before > np.sqrt(m)).sum() > 0       # the cap does something
    g = graphgen.build(seed=0, degree_cap="sqrt_edges", threads=2, **SMALL)
    assert g["indptr"][-1] == m == len(g["indices"])


def test_cache_rebuilds_on_key_change(tmp_path):
    cfg = dict(name="tiny", dataset_seed=1, degree_cap="sqrt_edges",
               **SMALL)
    logs = []
    a = graphgen.load_or_build(cfg, tmp_path, log=logs.append)
    b = graphgen.load_or_build(cfg, tmp_path, log=logs.append)
    assert logs[0].startswith("[graph] built")
    assert logs[1].startswith("[graph] loaded")
    assert all(np.array_equal(a[k], b[k]) for k in graphgen.ARRAYS)
    cfg2 = dict(cfg, dataset_seed=2)
    assert graphgen.cache_key(cfg2) != graphgen.cache_key(cfg)
    graphgen.load_or_build(cfg2, tmp_path, log=logs.append)
    assert logs[2].startswith("[graph] built")
    # the other dataset's twin stays
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        graphgen.cache_key(c) for c in (cfg, cfg2))
    json.dumps(graphgen.dataset_params(cfg2))


def test_configs_of_one_dataset_share_a_twin(tmp_path):
    """Two configurations that name one dataset (a GraphSAGE and another
    family, say) load one twin: the key holds the dataset alone."""
    sage = dict(name="tiny-sage", model="graphsage", hidden=32,
                dataset_seed=1, degree_cap="sqrt_edges", **SMALL)
    other = dict(sage, name="tiny-other", model="gcn", hidden=64)
    assert graphgen.cache_key(sage) == graphgen.cache_key(other)
    logs = []
    a = graphgen.load_or_build(sage, tmp_path, log=logs.append)
    b = graphgen.load_or_build(other, tmp_path, log=logs.append)
    assert [m.split()[1] for m in logs] == ["built", "loaded"]
    assert [p.name for p in tmp_path.iterdir()] == [graphgen.cache_key(sage)]
    assert all(np.array_equal(a[k], b[k]) for k in graphgen.ARRAYS)
