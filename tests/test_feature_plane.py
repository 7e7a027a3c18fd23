"""FeaturePlane seam: host/device parity (bit-exact fetch + identical
accounting), halo-leaf fills, resize/γ-swap under the device plane, and
the live ``sampling_device`` swap mid-run."""
import numpy as np
import pytest

from repro.core.a3gnn import A3GNNTrainer
from repro.core.cache import FeatureCache
from repro.core.feature_plane import (DeviceFeaturePlane, HostFeaturePlane,
                                      make_feature_plane)
from repro.core.pipeline import Pipeline
from repro.core.sampling import seed_loader


def _planes(graph, volume_mb=0.05, policy="static"):
    """A (host, device) plane pair over two independent but identically
    seeded caches — parity means the SAME request stream produces
    bit-identical rows and identical accounting on both."""
    ch = FeatureCache(graph, volume_mb, policy)
    cd = FeatureCache(graph, volume_mb, policy)
    return HostFeaturePlane(graph, ch), DeviceFeaturePlane(graph, cd)


def _stats_tuple(c: FeatureCache):
    s = c.stats
    return (s.hits, s.misses, s.evictions, s.bytes_from_cache,
            s.bytes_from_host)


# ---------------------------------------------------------------------------
# fetch parity: hits, misses, accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["static", "fifo"])
def test_fetch_parity_hits_and_misses(smoke_graph, policy):
    host, dev = _planes(smoke_graph, policy=policy)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, smoke_graph.num_nodes, 500)
    a, b = host.fetch(ids), dev.fetch(ids)
    assert a.dtype == b.dtype == np.float32
    assert np.array_equal(a, b)                       # bit-exact
    assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)
    # repeat fetch: static hits the same rows, FIFO hits inserted rows —
    # either way the two planes must keep agreeing
    a, b = host.fetch(ids[:128]), dev.fetch(ids[:128])
    assert np.array_equal(a, b)
    assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)
    np.testing.assert_array_equal(a, smoke_graph.features[ids[:128]])


def test_fetch_parity_pure_hit_and_pure_miss(smoke_graph):
    host, dev = _planes(smoke_graph)
    cached = np.where(host.cache.device_map >= 0)[0][:32]
    uncached = np.where(host.cache.device_map < 0)[0][:32]
    assert np.array_equal(host.fetch(cached), dev.fetch(cached))
    assert dev.cache.stats.misses == 0                # pure-hit batch
    assert np.array_equal(host.fetch(uncached), dev.fetch(uncached))
    assert dev.cache.stats.hits == len(cached)        # no false hits


def test_cacheless_and_zero_capacity_device_plane(smoke_graph):
    ids = np.arange(64)
    dev = DeviceFeaturePlane(smoke_graph, None)
    np.testing.assert_array_equal(dev.fetch(ids), smoke_graph.features[ids])
    assert dev.stats is None
    tiny = FeatureCache(smoke_graph, 0.0)             # capacity 0
    dev0 = DeviceFeaturePlane(smoke_graph, tiny)
    np.testing.assert_array_equal(dev0.fetch(ids), smoke_graph.features[ids])


def test_make_feature_plane_auto_probes_devices(smoke_graph):
    import jax
    plane = make_feature_plane(smoke_graph, None, "auto")
    on_tpu = jax.devices()[0].platform == "tpu"
    assert plane.backend == ("device" if on_tpu else "cpu")
    with pytest.raises(ValueError):
        make_feature_plane(smoke_graph, None, "gpu0")


# ---------------------------------------------------------------------------
# writes: halo-leaf rows through the plane
# ---------------------------------------------------------------------------

def test_fill_rows_updates_store_cache_and_mirror(smoke_graph):
    host, dev = _planes(smoke_graph, volume_mb=0.05)
    # pick one cache-resident and one non-resident row to overwrite
    resident = int(np.where(dev.cache.device_map >= 0)[0][0])
    absent = int(np.where(dev.cache.device_map < 0)[0][0])
    ids = np.array([resident, absent])
    host.fetch(ids)                                   # same stream on both;
    dev.fetch(ids)                                    # forces a device sync
    rows = np.full((2, smoke_graph.feat_dim), 7.5, np.float32)
    saved = smoke_graph.features[ids].copy()
    try:
        host.fill_rows(ids, rows)
        dev.fill_rows(ids, rows)
        for plane in (host, dev):
            got = plane.fetch(ids)                    # resident row must NOT
            np.testing.assert_array_equal(got, rows)  # serve the stale copy
        assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)
    finally:
        smoke_graph.features[ids] = saved             # session-scoped fixture


def test_multipartition_halo_fill_parity(smoke_graph, smoke_gnn_cfg):
    """Halo-leaf rows flow through the plane on both backends: the synced
    2-partition step is bit-exact cpu vs device, halo hits included."""
    import jax
    from repro.core.multipart import MultiPartitionTrainer
    cfg = smoke_gnn_cfg.replace(partitions=2, halo_budget=32)
    tc = MultiPartitionTrainer(smoke_graph, cfg.replace(
        sampling_device="cpu"), seed=0)
    td = MultiPartitionTrainer(smoke_graph, cfg.replace(
        sampling_device="device"), seed=0)
    try:
        assert tc.halo_exchange_bytes == td.halo_exchange_bytes > 0
        for _ in range(2):
            tc.global_step()
            td.global_step()
        for a, b in zip(jax.tree_util.tree_leaves(tc.params),
                        jax.tree_util.tree_leaves(td.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert tc.halo_hit_rate == td.halo_hit_rate > 0.0
        assert tc.cache_hit_rate == td.cache_hit_rate
    finally:
        for s in tc.slots + td.slots:
            s.pipe.shutdown()


# ---------------------------------------------------------------------------
# reconfiguration under the device plane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["static", "fifo"])
def test_resize_under_device_plane(smoke_graph, policy):
    host, dev = _planes(smoke_graph, volume_mb=0.05, policy=policy)
    ids = np.random.default_rng(1).integers(0, smoke_graph.num_nodes, 300)
    host.fetch(ids)
    dev.fetch(ids)
    old_table = dev._dev_table
    for vol in (0.1, 0.02):                           # grow, then shrink
        host.resize(vol)
        dev.resize(vol)
        assert np.array_equal(host.fetch(ids), dev.fetch(ids))
        assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)
    # the stale device buffers were donated (deleted), not leaked
    assert dev._dev_table is not old_table
    assert old_table.is_deleted()


def test_gamma_swap_under_device_plane(smoke_graph, smoke_gnn_cfg):
    """γ swap + Θ resize through apply_live_config with a device-plane
    pipeline: the bias weights see the SAME cache the device gathers."""
    cfg = smoke_gnn_cfg.replace(sampling_device="device", bias_rate=2.0)
    tr = A3GNNTrainer(smoke_graph, cfg, seed=0)
    pipe = tr.make_pipeline()
    try:
        assert pipe.sampling_device == "device"
        assert isinstance(pipe.plane, DeviceFeaturePlane)
        stats = pipe.run(max_steps=2)
        assert stats.steps == 2 and tr.cache.stats.hits > 0
        plane_before = pipe.plane
        tr.apply_live_config({"bias_rate": 8.0, "cache_volume_mb": 0.5}, pipe)
        assert pipe.plane.cache is tr.cache           # same accounting
        # same cache object + same backend → the plane (and its synced
        # mirror) survives the episode boundary instead of re-uploading
        assert pipe.plane is plane_before
        assert isinstance(pipe.plane, DeviceFeaturePlane)
        cached = np.where(tr.cache.device_map >= 0)[0][:8]
        np.testing.assert_allclose(tr.weight_fn(cached), 8.0)
        stats = pipe.run(max_steps=2)                 # resized mirror serves
        assert stats.steps == 2
    finally:
        pipe.shutdown()


# ---------------------------------------------------------------------------
# live sampling_device swap mid-run
# ---------------------------------------------------------------------------

def test_live_sampling_device_swap_drains_nothing_dropped(smoke_graph,
                                                          smoke_gnn_cfg):
    cfg = smoke_gnn_cfg.replace(parallel_mode="mode2", workers=2)
    tr = A3GNNTrainer(smoke_graph, cfg, seed=0)
    pipe = Pipeline(smoke_graph, cfg, tr._train_fn, cache=tr.cache,
                    weight_fn=tr.weight_fn, seed=0)
    try:
        batches = list(seed_loader(smoke_graph, cfg.batch_size, 0))[:6]
        pipe.begin_stats()
        pipe.submit(batches)
        for _ in range(2):
            assert pipe.step()
        assert pipe.inflight == 4
        pipe.reconfigure(sampling_device="device")    # drain → swap plane
        assert pipe.inflight == 0
        assert pipe.stats.steps == 6                  # nothing dropped
        assert pipe.sampling_device == "device"
        assert isinstance(pipe.plane, DeviceFeaturePlane)
        assert pipe.cache is tr.cache                 # accounting survived
        pipe.submit(batches[:2])                      # resumes on device
        pipe.drain()
        assert pipe.stats.steps == 8
        pipe.reconfigure(sampling_device="cpu")       # and back
        assert isinstance(pipe.plane, HostFeaturePlane)
        assert not isinstance(pipe.plane, DeviceFeaturePlane)
    finally:
        pipe.shutdown()


def test_device_plane_mode1_concurrent_workers(smoke_graph, smoke_gnn_cfg):
    """mode1 batch-gen workers share the device plane from multiple
    threads; the FIFO policy forces mirror re-uploads mid-run, so this
    exercises the sync-vs-gather lock (a lost race kills a worker and
    shows up as a re-issued batch)."""
    cfg = smoke_gnn_cfg.replace(parallel_mode="mode1", workers=3,
                                sampling_device="device",
                                cache_policy="fifo", cache_volume_mb=0.05)
    tr = A3GNNTrainer(smoke_graph, cfg, seed=0)
    pipe = tr.make_pipeline()
    try:
        assert isinstance(pipe.plane, DeviceFeaturePlane)
        stats = pipe.run(max_steps=8)
        assert stats.steps == 8
        assert stats.reissued == 0                    # no worker died
        assert tr.cache.stats.hits + tr.cache.stats.misses > 0
    finally:
        pipe.shutdown()


def test_device_plane_training_bit_exact_with_host(smoke_graph,
                                                   smoke_gnn_cfg):
    """The acceptance bar: same seed, same steps — device-plane training
    reproduces host-plane parameters bit-exactly."""
    import jax
    tc = A3GNNTrainer(smoke_graph, smoke_gnn_cfg.replace(
        sampling_device="cpu"), seed=0)
    td = A3GNNTrainer(smoke_graph, smoke_gnn_cfg.replace(
        sampling_device="device"), seed=0)
    rc = tc.run_epochs(1, max_steps_per_epoch=4)
    rd = td.run_epochs(1, max_steps_per_epoch=4)
    assert rc.stats.losses == rd.stats.losses
    assert rc.cache_hit_rate == rd.cache_hit_rate
    for a, b in zip(jax.tree_util.tree_leaves(tc.params),
                    jax.tree_util.tree_leaves(td.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_autotune_live_swaps_sampling_device(smoke_graph, smoke_gnn_cfg):
    """The controller drives the plane swap end-to-end: with the
    sampling_device knob gated on, episodes run on both backends and the
    trainer ends on the recommendation without dropping a batch."""
    from repro.configs.gnn import AutotuneConfig
    from repro.core.autotune.controller import AutotuneController
    tr = A3GNNTrainer(smoke_graph, smoke_gnn_cfg, seed=0)
    pipe = tr.make_pipeline()
    acfg = AutotuneConfig(episodes=3, steps_per_episode=3, warmup_steps=0,
                          presample=24, surrogate_trees=8, ppo_updates=1,
                          ppo_horizon=4, tune_sampling_device=True, seed=0)
    ctrl = AutotuneController(tr, pipe, acfg)
    try:
        rep = ctrl.run()
    finally:
        ctrl.pipe.shutdown()
    assert all(ep.config["sampling_device"] in ("cpu", "device")
               for ep in rep.episodes)
    assert all(ep.steps == 3 for ep in rep.episodes)  # no dropped batches
    assert tr.cfg.sampling_device == rep.best.config["sampling_device"]
    assert ctrl.pipe.sampling_device == rep.best.config["sampling_device"]


# ---------------------------------------------------------------------------
# incremental mirror sync: O(dirty rows), not O(capacity)
# ---------------------------------------------------------------------------

def test_incremental_sync_parity_and_upload_counters(smoke_graph):
    """Interleaved FIFO inserts + streamed update_rows keep the mirror
    coherent through row-wise scatters: bit-exact and stats-exact with the
    host plane AND with a full-reupload device plane, while full uploads
    happen exactly once (the initial upload) and the scattered-row volume
    stays O(dirty rows) — the whole-mirror re-upload pathology is gone."""
    from repro.graph.storage import FeatureStore
    host = HostFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.2, "fifo"))
    dev = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.2, "fifo"))
    full = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.2, "fifo"),
                              incremental_sync=False)
    store = FeatureStore(smoke_graph)
    for p in (host, dev, full):
        p.subscribe_to(store)
    rng = np.random.default_rng(3)
    saved = smoke_graph.features.copy()
    try:
        dirty_budget = 0
        for step in range(12):
            ids = rng.integers(0, smoke_graph.num_nodes, 48)
            a, b, c = host.fetch(ids), dev.fetch(ids), full.fetch(ids)
            assert np.array_equal(a, b) and np.array_equal(a, c)
            dirty_budget += 3 * 48          # slots + evicted + inserted ids
            if step % 3 == 1:               # interleave streamed updates
                resident = np.where(dev.cache.device_map >= 0)[0][:4]
                rows = rng.normal(0, 1, (len(resident),
                                         smoke_graph.feat_dim)).astype(np.float32)
                store.update_rows(resident, rows)
                dirty_budget += len(resident)
                for p in (host, dev, full):
                    np.testing.assert_array_equal(p.fetch(resident), rows)
        assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)
        assert _stats_tuple(host.cache) == _stats_tuple(full.cache)
        # THE upload-counter assertion: only the initial mirror upload was
        # a full table move; every version bump after it was a scatter
        assert dev.sync_full_uploads == 1
        assert dev.sync_row_scatters > 0
        assert dev.sync_rows_scattered <= dirty_budget          # O(dirty)
        assert dev.sync_rows_scattered < \
            dev.sync_row_scatters * dev.cache.capacity          # not O(cap)
        # the incremental-off twin re-uploaded the whole table every bump
        assert full.sync_full_uploads > 1 and full.sync_row_scatters == 0
        # ... and moved strictly more host→device bytes for the same stream
        assert dev.sync_bytes_uploaded < full.sync_bytes_uploaded
    finally:
        smoke_graph.features[:] = saved      # session-scoped fixture
        for p in (host, dev, full):
            p.detach_store()


def test_full_reupload_only_on_realloc(smoke_graph):
    """resize/realloc is the ONLY event that re-uploads the full table;
    FIFO-inserting fetches and patch_resident calls scatter rows."""
    dev = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.2, "fifo"))
    rng = np.random.default_rng(4)
    dev.fetch(rng.integers(0, smoke_graph.num_nodes, 64))
    assert dev.sync_full_uploads == 1        # the initial upload
    dev.fetch(rng.integers(0, smoke_graph.num_nodes, 64))
    assert dev.sync_full_uploads == 1        # FIFO insert → scatter only
    assert dev.sync_row_scatters >= 1
    resident = np.where(dev.cache.device_map >= 0)[0][:3]
    dev.fill_rows(resident, np.zeros((3, smoke_graph.feat_dim), np.float32))
    dev.fetch(resident)
    assert dev.sync_full_uploads == 1        # patch → scatter only
    dev.resize(0.1)
    dev.fetch(resident)
    assert dev.sync_full_uploads == 2        # realloc → full re-upload


def test_incremental_sync_falls_back_when_log_overflows(smoke_graph):
    """More dirty rows than the table holds → replay costs more than a
    full upload; the bounded delta log drops and the mirror re-uploads."""
    dev = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.01, "fifo"))
    rng = np.random.default_rng(5)
    cap = dev.cache.capacity
    dev.fetch(rng.integers(0, smoke_graph.num_nodes, 8))      # initial upload
    # one fetch inserting far more unique ids than capacity
    big = rng.permutation(smoke_graph.num_nodes)[:4 * cap]
    host = HostFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.01, "fifo"))
    host.fetch(rng.integers(0, smoke_graph.num_nodes, 8))
    assert np.array_equal(host.fetch(big), dev.fetch(big))
    # the oversized insert dropped the log; the NEXT sync (triggered by
    # the version bump the insert left behind) must be a full upload
    probe = np.arange(8)
    assert np.array_equal(host.fetch(probe), dev.fetch(probe))
    assert dev.sync_full_uploads == 2        # overflow → full, not scatter
    assert _stats_tuple(host.cache) == _stats_tuple(dev.cache)


def test_device_bytes_reports_resident_buffers(smoke_graph):
    """device_bytes is the ACTUAL HBM footprint: 0 before the first
    upload, table+slot-map bytes while resident, 0 again after delete."""
    dev = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.05))
    assert dev.device_bytes() == 0           # nothing uploaded yet
    dev.fetch(np.arange(32))
    expect = dev.cache.storage.nbytes + dev.cache.device_map.nbytes
    assert dev.device_bytes() == expect
    for buf in (dev._dev_table, dev._dev_slots):
        buf.delete()
    assert dev.device_bytes() == 0           # deleted buffers don't count
    # cacheless / zero-capacity planes have no mirror at all
    assert DeviceFeaturePlane(smoke_graph, None).device_bytes() == 0
    tiny = DeviceFeaturePlane(smoke_graph, FeatureCache(smoke_graph, 0.0))
    tiny.fetch(np.arange(8))
    assert tiny.device_bytes() == 0
