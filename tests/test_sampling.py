"""Locality-aware sampling: Algo. 2 oracle vs vectorized ES and the hub
rows' rejection path, bias effects, property-based invariants."""
from types import SimpleNamespace

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import sampling
from repro.core.sampling import (reservoir_sample_ref, es_sample,
                                 NeighborSampler, seed_loader)
from repro.core.cache import FeatureCache
from repro.core.locality import bias_weight_fn
from repro.graph.storage import Graph


@pytest.fixture
def hop_spans(monkeypatch):
    """The arguments of every ``sampler.hop`` span the sampler opens."""
    got = []

    class Rec:
        def __init__(self, **counts):
            self.args = dict(counts)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            got.append(self.args)

        def set_metadata(self, **counts):
            self.args.update(counts)

    monkeypatch.setattr(sampling, "span",
                        lambda name, **counts: Rec(**counts))
    return got


def _repeated_row_graph(nbrs: np.ndarray, rows: int) -> Graph:
    """``rows`` nodes (ids 0..rows-1) that each have the neighbour list
    ``nbrs`` (ids from ``rows`` up), so one hop over them is ``rows``
    independent draws from one row."""
    n = rows + int(nbrs.max()) + 1
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:rows + 1] = np.arange(1, rows + 1) * len(nbrs)
    indptr[rows + 1:] = indptr[rows]
    indices = np.tile(nbrs + rows, rows).astype(np.int32)
    return Graph(indptr=indptr, indices=indices,
                 features=np.zeros((n, 1), np.float32),
                 labels=np.zeros(n, np.int32),
                 train_mask=np.ones(n, bool), val_mask=np.zeros(n, bool),
                 test_mask=np.zeros(n, bool))


def test_reservoir_returns_all_when_small():
    rng = np.random.default_rng(0)
    nb = np.arange(5)
    w = np.ones(5)
    out = reservoir_sample_ref(nb, w, 10, rng)
    assert set(out) == set(nb)
    out = es_sample(nb, w, 10, rng)
    assert set(out) == set(nb)


@given(n=st.integers(6, 60), m=st.integers(1, 5), seed=st.integers(0, 999))
@settings(max_examples=30, deadline=None)
def test_sample_size_and_uniqueness(n, m, seed):
    rng = np.random.default_rng(seed)
    nb = np.arange(n) * 3
    w = rng.uniform(0.5, 5.0, n)
    for fn in (reservoir_sample_ref, es_sample):
        out = fn(nb, w, m, np.random.default_rng(seed))
        assert len(out) == m
        assert len(set(out.tolist())) == m          # no duplicates
        assert set(out.tolist()) <= set(nb.tolist())


def test_reservoir_and_es_same_distribution():
    """Both implement Efraimidis–Spirakis: selection frequencies match."""
    nb = np.arange(8)
    w = np.array([4.0, 4.0, 1, 1, 1, 1, 1, 1])
    m, trials = 2, 4000
    counts = {"ref": np.zeros(8), "es": np.zeros(8)}
    rng1, rng2 = np.random.default_rng(1), np.random.default_rng(2)
    for _ in range(trials):
        for key, fn, rng in (("ref", reservoir_sample_ref, rng1),
                             ("es", es_sample, rng2)):
            out = fn(nb, w, m, rng)
            counts[key][out] += 1
    f_ref = counts["ref"] / (trials * m)
    f_es = counts["es"] / (trials * m)
    # the two implementations agree within sampling noise
    np.testing.assert_allclose(f_ref, f_es, atol=0.03)
    # heavy nodes selected more often
    assert f_es[:2].mean() > 2.0 * f_es[2:].mean()


def test_bias_increases_cached_selection(smoke_graph):
    """γ > 1 must raise the fraction of sampled neighbors that are cached —
    the paper's core mechanism (Fig. 2b / Fig. 7)."""
    cache = FeatureCache(smoke_graph, volume_mb=0.05, policy="static")
    frac = {}
    for gamma in (1.0, 8.0):
        wfn = bias_weight_fn(cache, gamma)
        s = NeighborSampler(smoke_graph, (10,), weight_fn=wfn, seed=3)
        seeds = np.arange(200)
        mb = s.sample(seeds)
        picked = mb.blocks[0].src_ids
        frac[gamma] = cache.is_cached(picked).mean()
    assert frac[8.0] > frac[1.0]


def test_gamma_one_equals_uniform(smoke_graph, hop_spans):
    """γ=1 reverts to plain random sampling (same RNG → same picks), on
    the ES path and the hub rows' rejection path alike."""
    cache = FeatureCache(smoke_graph, volume_mb=0.05, policy="static")
    wfn = bias_weight_fn(cache, 1.0)
    s1 = NeighborSampler(smoke_graph, (5, 5), weight_fn=wfn, seed=7)
    s2 = NeighborSampler(smoke_graph, (5, 5), weight_fn=None, seed=7)
    seeds = np.arange(64)
    b1, b2 = s1.sample(seeds), s2.sample(seeds)
    for blk1, blk2 in zip(b1.blocks, b2.blocks):
        assert np.array_equal(blk1.src_ids, blk2.src_ids)
        assert np.array_equal(blk1.neigh_idx, blk2.neigh_idx)
    assert all(a["reject_rows"] > 0 for a in hop_spans)
    assert hop_spans[:2] == hop_spans[2:]


@pytest.mark.parametrize("gamma,dup", [(2.0, False), (8.0, False),
                                       (2.0, True)])
def test_rejection_and_reservoir_same_distribution(gamma, dup):
    """A hub row's picks by rejection follow Algo. 2: selection
    frequencies of two-class rows (``gamma`` on the first 6 ids, 1 on the
    rest) of degree above REJECT_FACTOR · fanout · γ agree within sampling
    noise.  With ``dup`` every cached id is a double edge: the two entries
    are two items, so an id may be picked twice, as under Algo. 2."""
    m = 3
    d = int(sampling.REJECT_FACTOR * m * gamma) + 4
    nbrs = np.arange(d)
    if dup:
        nbrs[6:12] = nbrs[:6]
    cached = np.zeros(d, bool)
    cached[:6] = True
    rows, trials = 20_000, 4_000
    g = _repeated_row_graph(nbrs, rows)
    cache = SimpleNamespace(device_map=np.where(
        np.concatenate([np.zeros(rows, bool), cached]), 0, -1))
    s = NeighborSampler(g, (m,), weight_fn=bias_weight_fn(cache, gamma),
                        seed=5)
    out, hubs, proposals = s._sample_one_hop(np.arange(rows), m)
    assert hubs == rows and proposals >= rows * m
    f_rej = np.bincount(out.ravel() - rows, minlength=d) / (rows * m)
    w = np.where(cached[nbrs], gamma, 1.0)
    rng = np.random.default_rng(6)
    f_ref = np.zeros(d)
    for _ in range(trials):
        np.add.at(f_ref, reservoir_sample_ref(nbrs, w, m, rng), 1)
    f_ref /= trials * m
    np.testing.assert_allclose(f_rej, f_ref, atol=0.012)
    assert f_rej[:6].sum() == pytest.approx(f_ref[:6].sum(), abs=0.015)


@pytest.mark.parametrize("gamma", [1.0, 2.0, 8.0])
def test_rejection_picks_are_distinct_neighbours(smoke_graph, gamma):
    """Every row past the threshold gets ``fanout`` picks, each a
    neighbour, no position twice (an id at most as often as the row holds
    it); rows at most ``fanout`` wide keep all their neighbours."""
    cache = FeatureCache(smoke_graph, volume_mb=0.05, policy="static")
    s = NeighborSampler(smoke_graph, (5,),
                        weight_fn=bias_weight_fn(cache, gamma), seed=1)
    dst = np.arange(smoke_graph.num_nodes)
    out, hubs, _ = s._sample_one_hop(dst, 5)
    assert hubs > 0
    for v, row in zip(dst, out):
        nb = smoke_graph.neighbors(v)
        got = row[row >= 0]
        assert len(got) == min(5, len(nb))
        ids, have = np.unique(nb, return_counts=True)
        picked, times = np.unique(got, return_counts=True)
        assert np.isin(picked, ids).all()
        assert (times <= have[np.searchsorted(ids, picked)]).all()


def test_weights_without_bounds_take_es_only(smoke_graph, hop_spans,
                                             monkeypatch):
    """A weight function that declares no bounds samples by ES keys on
    every row, exactly as the bounded one does with the rejection path
    turned off."""
    cache = FeatureCache(smoke_graph, volume_mb=0.05, policy="static")
    bounded = bias_weight_fn(cache, 4.0)

    def bare(ids):
        return bounded(ids)
    seeds = np.arange(64)
    plain = NeighborSampler(smoke_graph, (5, 5), weight_fn=bare,
                            seed=3).sample(seeds)
    assert [a["reject_rows"] for a in hop_spans] == [0, 0]
    assert [a["proposals"] for a in hop_spans] == [0, 0]
    NeighborSampler(smoke_graph, (5, 5), weight_fn=bounded,
                    seed=3).sample(seeds)
    assert hop_spans[-1]["reject_rows"] > 0
    monkeypatch.setattr(sampling, "REJECT_FACTOR", np.inf)
    es = NeighborSampler(smoke_graph, (5, 5), weight_fn=bounded,
                         seed=3).sample(seeds)
    for a, b in zip(plain.blocks, es.blocks):
        assert np.array_equal(a.src_ids, b.src_ids)
        assert np.array_equal(a.neigh_idx, b.neigh_idx)


def test_blocks_wellformed(smoke_graph):
    s = NeighborSampler(smoke_graph, (5, 3), seed=0)
    seeds = np.arange(32)
    mb = s.sample(seeds)
    assert len(mb.blocks) == 2
    # output hop: dst == seeds
    assert np.array_equal(mb.blocks[-1].dst_ids, seeds)
    for blk in mb.blocks:
        # dst ids form the prefix of src ids
        assert np.array_equal(blk.src_ids[:len(blk.dst_ids)], blk.dst_ids)
        # neighbor indices inside range
        v = blk.neigh_idx[blk.neigh_idx >= 0]
        assert v.size == 0 or v.max() < len(blk.src_ids)
        # sampled ids resolve to actual graph neighbors
        for i in range(min(5, len(blk.dst_ids))):
            nbrs = set(smoke_graph.neighbors(blk.dst_ids[i]).tolist())
            got = blk.neigh_idx[i][blk.neigh_idx[i] >= 0]
            assert set(blk.src_ids[got].tolist()) <= nbrs
    # chain: hop i src == hop i-1 ... (blocks input-first)
    for a, b in zip(mb.blocks[:-1], mb.blocks[1:]):
        assert np.array_equal(a.dst_ids, b.src_ids)


def test_seed_loader_partitions_train_nodes(smoke_graph):
    batches = list(seed_loader(smoke_graph, 64, seed=0))
    allv = np.concatenate(batches)
    assert len(np.unique(allv)) == len(allv)          # no repeats
    assert smoke_graph.train_mask[allv].all()
