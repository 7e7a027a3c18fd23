"""bench/trace.py: busy union, idle share, kernel time, roofline share,
gaps, and the reduction of a small trace recorded on a TPU v5e."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

OPS = [("fusion.1", 100, 200), ("cache_gather", 150, 300),
       ("cache_gather.7", 500, 600), ("copy", 900, 1000),
       ("cache_gathered", 950, 960)]
SPANS = [("bench.window", 0, 1000), ("bench.sample", 300, 500),
         ("bench.fetch", 600, 900), ("bench.step", 0, 100)]


def test_busy_union_and_idle_share():
    iv = [(s, e) for _, s, e in OPS]
    assert trace.merge(iv, 0, 1000) == [(100, 300), (500, 600), (900, 1000)]
    assert trace.busy_ns(iv, 0, 1000) == 400
    assert trace.busy_ns(iv, 120, 550) == 180 + 50
    s = trace.summarize({"ops": {"/device:TPU:0": OPS}, "spans": SPANS},
                        kernels=("cache_gather",))
    assert s["window_s"] == pytest.approx(1e-6)
    assert s["busy_s"] == pytest.approx(400e-9)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.6)


def test_kernel_time_matches_names_exactly():
    assert trace.kernel_ns(OPS, "cache_gather", 0, 1000) == 150 + 100
    assert trace.kernel_ns(OPS, "cache_gather", 200, 1000) == 100 + 100


def test_gaps_named_by_covering_span():
    gaps = trace.idle_gaps(OPS, SPANS, 0, 1000)
    assert gaps[0] == ["fetch", pytest.approx(300e-9)]
    assert gaps[1] == ["sample", pytest.approx(200e-9)]
    assert gaps[2] == ["step", pytest.approx(100e-9)]


def test_roofline_share_on_known_bytes():
    spec = importlib.util.spec_from_file_location(
        "roof", ROOT / "bench/metrics/gather_roofline.train.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # 1000 rows of F=100, 150 hits: 150 * 400 read + 1000 * 404 written,
    # 464,000 B at 819 GB/s = 566.5 ns; in 2 us: 28.33%
    ctx = {"trace": {"kernel_s": {"cache_gather": 2e-6}},
           "gather_rows": 1000, "hits": 150, "feat_dim": 100,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert mod.read(ctx) == pytest.approx(100 * 464000 / 819e9 / 2e-6)
    ctx["trace"] = None
    assert mod.read(ctx) is None


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: three rounds of a 2,048-row
    ``cache_gather`` (span ``fetch``), a 2 ms sleep (``sample``) and a
    small matmul (``step``) inside a ``window`` span."""
    t = trace.read(Path(__file__).parent / "data" / "tiny_v5e.xplane.pb")
    assert list(t["ops"]) == ["/device:TPU:0"]
    s = trace.summarize(t, kernels=("cache_gather",))
    assert s["window_s"] == pytest.approx(0.016118298)
    assert s["busy_s"] == pytest.approx(5.102e-05)
    assert s["kernel_s"]["cache_gather"] == pytest.approx(1.9242e-05)
    assert s["breakdown"]["device_ops"][0] == ["cache_gather.1",
                                              pytest.approx(1.9242e-05)]
    assert [g[0] for g in s["breakdown"]["idle_gaps"][:3]] == \
        ["sample", "sample", "fetch"]
