"""The reader of ``sampler_accept_rate.train``: Σpicks / Σproposals of the
window's ``a3gnn.sampler.hop`` spans, on synthetic spans and on a real
sampler's, and None where no row took the rejection path, without a
trace, or on a trace without program spans."""
import importlib.util
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spans  # noqa: E402


def _reader():
    spec = importlib.util.spec_from_file_location(
        "sampler_accept_rate_train",
        ROOT / "bench" / "metrics" / "sampler_accept_rate.train.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


read = _reader()


def _trace(trace_dir, before, inside) -> Path:
    """A CPU trace: ``before()`` ahead of ``bench.window``, ``inside()``
    in it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        before()
        with jax.profiler.TraceAnnotation("bench.window"):
            inside()
    finally:
        jax.profiler.stop_trace()
    return sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]


def _hops(*args):
    from jax.profiler import TraceAnnotation

    def run():
        for rows, reject_rows, proposals in args:
            with TraceAnnotation("a3gnn.sampler.hop", rows=rows,
                                 reject_rows=reject_rows,
                                 proposals=proposals, picks=reject_rows * 5):
                time.sleep(1e-3)
    return run


def test_synthetic_hops(tmp_path):
    """A hop with no row on the rejection path adds nothing, a hop before
    the window is not read, a window without a proposal reads None."""
    path = _trace(tmp_path / "a", _hops((9, 9, 45)),
                  _hops((512, 40, 330), (7_000, 0, 0),
                        (70_000, 3_000, 26_000)))
    assert read({"xplane": path}) == pytest.approx(
        100 * (200 + 15_000) / (330 + 26_000))
    path = _trace(tmp_path / "b", _hops((9, 9, 45)), _hops((512, 0, 0)))
    assert read({"xplane": path}) is None


def test_real_sampler_hops(tmp_path, monkeypatch):
    """The program's own spans: a γ-biased sampler over a power-law graph
    whose hub rows take the rejection path."""
    from repro.configs.gnn import gnn_config
    from repro.core.cache import FeatureCache
    from repro.core.locality import bias_weight_fn
    from repro.core.sampling import NeighborSampler
    from repro.graph.synthetic import dataset_like
    graph = dataset_like(gnn_config("products", smoke=True), seed=0)
    cache = FeatureCache(graph, volume_mb=0.05, policy="static")
    sampler = NeighborSampler(graph, (5, 5),
                              weight_fn=bias_weight_fn(cache, 2.0), seed=0)
    path = _trace(tmp_path, lambda: None,
                  lambda: [sampler.sample(np.arange(64 * i, 64 * i + 64))
                           for i in range(4)])
    rec = spans.entry({"xplane": path}, "sampler.hop")
    assert rec["n"] == 8
    picks = sum(a["picks"] for a in rec["args"])
    proposals = sum(a["proposals"] for a in rec["args"])
    assert picks == 5 * sum(a["reject_rows"] for a in rec["args"]) > 0
    assert read({"xplane": path}) == pytest.approx(100 * picks / proposals)
    assert 0 < read({"xplane": path}) <= 100
    # no trace; a v5e trace with no program span
    monkeypatch.setattr(spans, "TRACES", tmp_path / "none")
    assert read({}) is None
    assert read({"xplane": Path(__file__).parent / "data"
                 / "tiny_v5e.xplane.pb"}) is None
