"""Parallelism scheduling: modes, fault injection, perf models."""
import numpy as np
import pytest

from repro.core.a3gnn import A3GNNTrainer
from repro.core.perf_model import (StageTimes, MemoryTerms, throughput_seq,
                                   throughput_mode1, throughput_mode2,
                                   memory_seq, memory_mode1, memory_mode2)


@pytest.fixture(scope="module")
def trainer(smoke_graph, smoke_gnn_cfg):
    return A3GNNTrainer(smoke_graph, smoke_gnn_cfg, seed=0)


@pytest.mark.slow
def test_all_modes_complete_and_learn(smoke_graph, smoke_gnn_cfg):
    for mode in ("seq", "mode1", "mode2"):
        tr = A3GNNTrainer(smoke_graph,
                          smoke_gnn_cfg.replace(parallel_mode=mode, workers=2),
                          seed=0)
        res = tr.run_epochs(1, max_steps_per_epoch=12)
        assert res.stats.steps == 12
        assert np.isfinite(res.stats.losses).all()
        assert res.stats.losses[-1] < res.stats.losses[0]


def test_worker_failure_reissued(smoke_graph, smoke_gnn_cfg, caplog):
    """A dying sampler worker must not lose work items (node-failure path),
    and every failure is logged with its exception, not swallowed."""
    cfg = smoke_gnn_cfg.replace(parallel_mode="mode1", workers=2)
    tr = A3GNNTrainer(smoke_graph, cfg, seed=0)
    with caplog.at_level("WARNING", logger="repro.core.pipeline"):
        res = tr.run_epochs(1, max_steps_per_epoch=10, fail_worker=0)
    assert res.stats.steps == 10            # all steps completed
    assert res.stats.reissued >= 1          # failed items re-issued
    failures = [r for r in caplog.records if "re-issuing" in r.getMessage()]
    assert len(failures) == res.stats.reissued
    assert all(r.exc_info and "injected failure" in str(r.exc_info[1])
               for r in failures)


def test_memory_model_ordering():
    """Eq. (3)/(5): mode1 ≥ mode2 ≥ seq for n ≥ 1 workers."""
    mt = MemoryTerms(cache_bytes=40e6, batch_bytes=30e6, model_bytes=100e6,
                     runtime_bytes=64e6)
    for n in (1, 2, 4, 8):
        m1 = memory_mode1(mt, n)
        m2 = memory_mode2(mt, n)
        ms = memory_seq(mt)
        assert m1 >= m2 >= ms
    # memory grows with workers in both parallel modes
    assert memory_mode1(mt, 4) > memory_mode1(mt, 1)
    assert memory_mode2(mt, 4) > memory_mode2(mt, 1)


def test_throughput_model_amdahl():
    """Eq. (2)/(4): more workers help until the serial stage dominates."""
    st = StageTimes(t_sample=0.08, t_batch=0.02, t_train=0.05)
    seq = throughput_seq(st, 10)
    m1 = [throughput_mode1(st, n, 10) for n in (1, 2, 4, 16)]
    m2 = [throughput_mode2(st, n, 10) for n in (1, 2, 4, 16)]
    assert all(b >= a for a, b in zip(m1, m1[1:]))
    assert m1[-1] == throughput_mode1(st, 64, 10)   # saturated at t_train
    assert m1[-1] >= m2[-1] >= seq
    # mode1 saturation = 1/t_train
    assert np.isclose(m1[-1], 1.0 / (st.t_train * 10))


@pytest.mark.slow
def test_modeled_memory_matches_mode(smoke_graph, smoke_gnn_cfg):
    r = {}
    for mode in ("seq", "mode1", "mode2"):
        tr = A3GNNTrainer(smoke_graph,
                          smoke_gnn_cfg.replace(parallel_mode=mode, workers=3),
                          seed=0)
        res = tr.run_epochs(1, max_steps_per_epoch=4)
        r[mode] = res.memory_bytes
    assert r["mode1"] >= r["mode2"] >= r["seq"]
