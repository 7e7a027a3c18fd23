"""FeaturePlane — the pluggable feature-fetch seam of the batch-generation
hot path (paper §III-A/B; the "gather" stage of sample → gather → transfer).
Training (core/pipeline.py) and online inference serving
(serve/gnn_engine.py) fetch through the SAME plane object, so the γ/Θ
cache and its hit/miss accounting carry across the train → serve boundary.

Every consumer of node features goes through ONE interface:

  * ``HostFeaturePlane``   — today's numpy path: ``FeatureCache.fetch``
    when a cache is configured, a direct host-store gather otherwise.
    Bit-exact with the pre-plane code (the regression anchor).
  * ``DeviceFeaturePlane`` — the cache table and the slot map (device map)
    live as jax device arrays; a batch fetch gathers resident rows on
    device — with the Pallas kernel (``kernels/gather.cache_gather``) when
    the plane's device is a TPU, the jnp gather elsewhere — and reads
    misses from the host feature store.  Accounting, FIFO insertion and
    resize semantics are delegated to the SAME ``FeatureCache``
    bookkeeping, so the two planes are bit-exact and stats-exact on the
    same request stream.

``make_feature_plane`` picks the backend from
``GNNConfig.sampling_device`` (``cpu | device | auto`` — auto probes
``jax.devices()`` and chooses the device plane only when a TPU is
attached, and logs its choice; the device plane still RUNS on CPU hosts,
which is what the parity tests exercise).

Reconfiguration contract (the autotune controller's live swaps):

  * ``resize``/γ-swap — the underlying ``FeatureCache`` mutates in place;
    the device plane detects the mutation through ``FeatureCache.version``
    and re-uploads, DELETING the stale device buffers first (the donation
    step — a live Θ sweep must not accumulate dead cache tables in HBM).
  * plane swap — ``Pipeline.reconfigure(sampling_device=...)`` drains the
    executor and rebuilds the plane around the same cache object, so
    hit/miss accounting survives a cpu↔device migration.
"""
from __future__ import annotations

import logging
import threading
from contextlib import contextmanager
from typing import Optional

import numpy as np

from repro.core.cache import FeatureCache
from repro.core.spans import span
from repro.graph.storage import Graph
from repro.kernels.pad_plan import bucket_plan

log = logging.getLogger(__name__)

# device-plane gather is issued in bounded row chunks: each distinct padded
# shape costs one jit trace and compile, so chunking plus pow2 bucketing
# of the tail keeps the set of compiled shapes small and
# independent of the batch-size schedule.  4096 covers the paper's batch
# regime in ONE dispatch — per-chunk dispatch overhead, not gather
# bandwidth, dominates the device plane's fixed cost
GATHER_CHUNK_ROWS = 4096


def _bucket(n: int) -> int:
    """Round ``n`` up to a pow2 (≥ 8) so jit retraces stay bounded —
    memoized through the shared pad-plan cache (kernels/pad_plan.py)."""
    return bucket_plan(n)


def _scatter_update(buf, idx, vals):
    """Dirty-row scatter into a device mirror buffer.  ``idx`` is padded
    to a pow2 length with out-of-range indices, which ``mode="drop"``
    discards; the input buffer is donated, so the update is in-place-like
    and never holds two live copies of the cache table in HBM."""
    return buf.at[idx].set(vals, mode="drop")


_scatter_update_jit = None


def _scatter(buf, idx, vals):
    global _scatter_update_jit
    if _scatter_update_jit is None:
        import functools
        import jax
        _scatter_update_jit = functools.partial(jax.jit, donate_argnums=(0,))(
            _scatter_update)
    return _scatter_update_jit(buf, idx, vals)


def _fused_pack_impl(aux, idx, vals, enc):
    """Single-dispatch step-input packing: scatter the miss rows into the
    donated sideband and move the encoding to device ALONGSIDE, in one
    jitted call.  Per-dispatch overhead (~100 µs on this container) is
    what made the old 3-conversions-plus-scatter sequence dominate
    small-batch cost — one dispatch instead of four is most of the
    small-batch win.  ``idx`` pads to a pow2 bucket with out-of-range
    entries (``mode="drop"``); padded ``vals`` rows are dropped with
    them, so their (uninitialized) contents never land in the buffer."""
    return enc, aux.at[idx].set(vals, mode="drop")


_fused_pack_jit = None


def _fused_pack(aux, idx, vals, enc):
    global _fused_pack_jit
    if _fused_pack_jit is None:
        import functools
        import jax
        _fused_pack_jit = functools.partial(jax.jit, donate_argnums=(0,))(
            _fused_pack_impl)
    return _fused_pack_jit(aux, idx, vals, enc)


def _host_pack_impl(enc, aux):
    """Host twin of ``_fused_pack``: one dispatch moves the all-sideband
    encoding + rows to the step, instead of one conversion each."""
    return enc, aux


_host_pack_jit = None


def _host_pack(enc, aux):
    global _host_pack_jit
    if _host_pack_jit is None:
        import jax
        _host_pack_jit = jax.jit(_host_pack_impl)
    return _host_pack_jit(enc, aux)


def _run_fused(enc, neigh_idx, table, aux, use_pallas: bool,
               mode: str = "mean"):
    """Bucket the fused gather+aggregate inputs to pow2 row counts (jit
    retraces stay bounded across the batch-size schedule) and slice the
    padding back off.  ``enc`` pads with -1 (→ ``aux[0]``, never referenced
    by a real dst row); neighbor rows pad with -1 (masked)."""
    import jax.numpy as jnp
    from repro.kernels.fused_gather_agg.ops import gather_aggregate
    ns = len(enc)
    nd, fan = neigh_idx.shape
    nsp, ndp = _bucket(ns), _bucket(nd)
    enc_p = np.full(nsp, -1, np.int32)
    enc_p[:ns] = enc
    idx_p = np.full((ndp, fan), -1, np.int32)
    idx_p[:nd] = neigh_idx
    nap = _bucket(max(len(aux), 1))
    aux_p = np.zeros((nap, aux.shape[1]), np.float32)
    aux_p[:len(aux)] = aux
    h, a = gather_aggregate(jnp.asarray(enc_p), jnp.asarray(idx_p),
                            jnp.asarray(table), jnp.asarray(aux_p),
                            mode=mode, use_pallas=use_pallas)
    return np.asarray(h)[:nd], np.asarray(a)[:nd]


class FeaturePlane:
    """Backend-pluggable feature-fetch interface (host implementation).

    ``fetch`` is the hot-path read (through the cache, with accounting);
    ``fill_rows`` is the write side used by the halo exchange — it updates
    the host store AND any cache-resident copy of the written rows, so a
    fill is visible no matter which backend serves the next fetch.
    """

    backend = "cpu"

    def __init__(self, graph: Graph, cache: Optional[FeatureCache] = None):
        self.graph = graph
        self.cache = cache
        self.store = None               # attached FeatureStore (subscribe_to)
        # per-batch gather counters — the read-side twin of the device
        # plane's sync_* upload counters.  Every plane read (``fetch``,
        # the fused ``gather_aggregate`` read, the step-time
        # ``fused_inputs``) ticks them: ``gather_dispatches`` counts
        # gather invocations (device plane: one per kernel dispatch, so
        # "one dispatch per batch" is an assertable claim; host plane:
        # one per plane call, the numpy gather has no finer dispatch
        # granularity); ``gather_rows`` counts the rows those dispatches
        # resolved.
        self.gather_dispatches = 0
        self.gather_rows = 0
        self._fused_table = None        # host fused_inputs' 1-row dummy

    # -- reads ---------------------------------------------------------------
    def fetch(self, ids: np.ndarray) -> np.ndarray:
        """Gather features for ``ids`` (n,) → (n, F) float32."""
        self.gather_dispatches += 1
        self.gather_rows += len(ids)
        if self.cache is not None:
            return self.cache.fetch(ids)
        return self.graph.features[np.asarray(ids, dtype=np.int64)]

    def gather_aggregate(self, ids: np.ndarray, neigh_idx: np.ndarray,
                         mode: str = "mean"):
        """Fused layer-0 read (``GNNConfig.fused_gather_agg``): resolve the
        input-hop rows and the masked neighbor aggregate (``mode``: mean
        or sum) in one kernel call, returning ``(h_dst (n_dst, F), agg
        (n_dst, F))`` where ``n_dst = neigh_idx.shape[0]`` (dst ids are
        the prefix of ``ids``).

        Host backend: fetch through the cache (same accounting as
        ``fetch`` — stats-exactness is a tested invariant) and run the
        SAME jitted fused op with an all-sideband encoding, so both
        backends compute the aggregate from bitwise-identical resolved
        rows — the cpu/device bit-exactness anchor."""
        ids = np.asarray(ids, dtype=np.int64)
        rows = self.fetch(ids)           # counts the gather_* traffic
        enc = -np.arange(1, len(ids) + 1, dtype=np.int32)
        table = np.zeros((1, self.graph.feat_dim), np.float32)
        return _run_fused(enc, neigh_idx, table, rows,
                          use_pallas=False, mode=mode)

    def fused_inputs(self, ids: np.ndarray, cap: int):
        """Encoded layer-0 inputs for the all-hop fused train step
        (models/gnn.py ``make_train_step_allfused``): ``(enc (cap,) int32,
        aux (cap, F) float32, table)`` padded to the FIXED input-level cap
        (graph/batch.py ``compute_level_caps``) so every batch hits one
        jit signature.  Padded enc entries are -1 → ``aux[0]``, never
        referenced by a real dst row.

        Host backend: all-sideband encoding — rows are fetched through the
        cache (same accounting as ``fetch``), ``enc[i] = -(i+1)`` and the
        table is a 1-row dummy, so the step resolves bitwise-identical
        rows to the device plane's slot encoding."""
        import jax.numpy as jnp
        ids = np.asarray(ids, dtype=np.int64)
        n = len(ids)
        if n > cap:
            raise ValueError(f"{n} input ids exceed level cap {cap}")
        rows = self.fetch(ids)           # counts the gather_* traffic
        enc = np.full(cap, -1, np.int32)
        enc[:n] = -np.arange(1, n + 1, dtype=np.int32)
        aux = np.zeros((cap, self.graph.feat_dim), np.float32)
        aux[:n] = rows
        if self._fused_table is None:
            self._fused_table = jnp.zeros((1, self.graph.feat_dim),
                                          jnp.float32)
        enc_dev, aux_dev = _host_pack(enc, aux)
        return enc_dev, aux_dev, self._fused_table

    # -- writes (halo fills / streaming updates) -----------------------------
    def subscribe_to(self, store) -> "FeaturePlane":
        """Wire this plane into a ``graph/storage.py`` ``FeatureStore``:
        every streamed ``update_rows`` patches cache-resident copies and
        invalidates device mirrors (the store itself already wrote the
        host rows), so the serving engine (serve/gnn_engine.py) and a
        live trainer observe the same drift through the same seam.  Any
        previous subscription is detached first (a plane tracks at most
        one store); the store is recorded so a plane swap
        (``Pipeline.reconfigure``) can migrate the subscription to the
        successor plane."""
        self.detach_store()
        self.store = store
        store.subscribe(self._on_store_update)
        return self

    def detach_store(self):
        """Unsubscribe from the attached store — a REPLACED plane must
        detach or streamed updates keep routing into the dead object
        while its successor's cache silently drifts
        (``Pipeline.reconfigure`` migrates the subscription)."""
        if self.store is not None:
            self.store.unsubscribe(self._on_store_update)
            self.store = None

    def _on_store_update(self, ids: np.ndarray, rows: np.ndarray):
        """Store subscriber: the store wrote the host rows already, so
        only resident copies need patching (version bump → mirror
        re-sync) — no redundant host-store rewrite per subscribed plane.
        A plane over a SUBGRAPH may be subscribed to a full-graph store
        (a fabric replica's plane, a test rig); ids outside this plane's
        node universe have no copy here and are dropped, not an error."""
        c = self.cache
        if c is not None:
            ids = np.asarray(ids, dtype=np.int64)
            rows = np.asarray(rows, dtype=np.float32)
            in_universe = ids < self.graph.num_nodes
            if not in_universe.all():
                ids, rows = ids[in_universe], rows[in_universe]
            if len(ids):
                c.patch_resident(ids, rows)

    def fill_rows(self, ids: np.ndarray, rows: np.ndarray):
        """Overwrite feature rows ``ids`` in the host store, propagating to
        cache-resident copies (and, on the device plane, invalidating the
        device mirror)."""
        ids = np.asarray(ids, dtype=np.int64)
        self.graph.features[ids] = rows
        c = self.cache
        if c is not None:
            # resident-copy patch + version bump (mirror invalidation)
            # live in ONE place: FeatureCache.patch_resident
            c.patch_resident(ids, np.asarray(rows, dtype=np.float32))

    # -- reconfiguration -----------------------------------------------------
    def resize(self, volume_mb: float, keep_residents: bool = True):
        """Episode-boundary Θ swap, routed through the plane so backend
        state (device mirrors) tracks the cache."""
        if self.cache is not None:
            self.cache.resize(volume_mb, keep_residents=keep_residents)

    @property
    def stats(self):
        return self.cache.stats if self.cache is not None else None


# back-compat alias: the host plane IS the base implementation
HostFeaturePlane = FeaturePlane


class DeviceFeaturePlane(FeaturePlane):
    """Device-resident gather: slot map + cache table as jax arrays, batch
    lookup through the Pallas ``cache_gather`` kernel, miss fallback to the
    host feature store.

    The ``FeatureCache`` object stays the single source of truth for the
    slot assignment, the replacement policy and the hit/miss accounting —
    this plane mirrors (storage, device_map) to the device and keeps the
    mirror coherent through the cache's dirty-row delta log
    (``FeatureCache.deltas_since``): a FIFO-inserting fetch or a streamed
    ``patch_resident`` scatters only the dirty rows into the live buffers
    (donated, so no second table materializes); a full delete + re-upload
    happens only on reallocation (``resize``/``_alloc``) or when the
    bounded log was dropped.  The mirror lives on ``device`` (default: the
    first device).  On a TPU the gathers always run the compiled Pallas
    kernels; on any other device they run the jitted jnp reference (the
    kernels' interpret mode is exercised by the kernel tests).
    """

    backend = "device"

    def __init__(self, graph: Graph, cache: Optional[FeatureCache] = None,
                 incremental_sync: bool = True, device=None):
        super().__init__(graph, cache)
        import jax
        self.device = device if device is not None else jax.devices()[0]
        self.use_pallas = self.device.platform == "tpu"
        self.incremental_sync = incremental_sync
        self._dev_table = None
        self._dev_slots = None
        self._version = -1
        self._epoch = -1
        # mirror-maintenance counters (the upload-counter test and
        # benchmarks/fig_gather.py read these): full uploads move
        # O(capacity) rows, scatters move O(dirty rows)
        self.sync_full_uploads = 0
        self.sync_row_scatters = 0
        self.sync_rows_scattered = 0
        self.sync_bytes_uploaded = 0    # host→device mirror traffic, exact
        # per-cap persistent aux sidebands for the all-hop fused path:
        # miss rows are scattered into a donated device buffer instead of
        # re-uploading a (cap, F) tensor per batch — the whole point of
        # the encoded-slot contract is that per-batch feature traffic is
        # O(misses), not O(cap)
        self._aux_bufs = {}
        # mode1 batch-gen workers share the plane: the mirror delete +
        # re-upload must never race a gather in another thread (a deleted
        # buffer mid-kernel is fatal, unlike the host path's benign numpy
        # interleavings), so sync + gather + insert run under one lock
        self._lock = threading.Lock()

    @contextmanager
    def _locked(self):
        """Hold the plane's lock; the wait for it is the
        ``plane.lock_wait`` span."""
        with span("plane.lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    # -- device mirror -------------------------------------------------------
    def _ensure_synced(self):
        c = self.cache
        if self._dev_table is not None and self._version == c.version:
            return
        import jax
        import jax.numpy as jnp
        deltas = (c.deltas_since(self._version, self._epoch)
                  if self.incremental_sync and self._dev_table is not None
                  else None)
        if deltas is None:
            # reallocation (or the bounded delta log was dropped): the
            # buffer shapes may have changed — delete the stale mirror
            # and re-upload the whole table
            for buf in (self._dev_table, self._dev_slots):
                if buf is not None:
                    buf.delete()
            self._dev_table = jax.device_put(c.storage, self.device)
            self._dev_slots = jax.device_put(c.device_map, self.device)
            self.sync_full_uploads += 1
            self.sync_bytes_uploaded += (c.storage.nbytes
                                         + c.device_map.nbytes)
        else:
            dirty_slots, dirty_ids = deltas
            if len(dirty_slots):
                # pad to a pow2 with out-of-range indices (dropped by the
                # scatter) so jit retraces stay bounded
                p = _bucket(len(dirty_slots))
                idx = np.full(p, c.capacity, np.int32)
                idx[:len(dirty_slots)] = dirty_slots
                vals = np.zeros((p, self.graph.feat_dim), np.float32)
                vals[:len(dirty_slots)] = c.storage[dirty_slots]
                self._dev_table = _scatter(self._dev_table,
                                           jnp.asarray(idx),
                                           jnp.asarray(vals))
                self.sync_bytes_uploaded += vals.nbytes + idx.nbytes
            if len(dirty_ids):
                p = _bucket(len(dirty_ids))
                idx = np.full(p, self.graph.num_nodes, np.int64)
                idx[:len(dirty_ids)] = dirty_ids
                vals = np.zeros(p, np.int32)
                vals[:len(dirty_ids)] = c.device_map[dirty_ids]
                self._dev_slots = _scatter(self._dev_slots,
                                           jnp.asarray(idx),
                                           jnp.asarray(vals))
                self.sync_bytes_uploaded += vals.nbytes + idx.nbytes
            self.sync_row_scatters += 1
            self.sync_rows_scattered += len(dirty_slots) + len(dirty_ids)
        self._version = c.version
        self._epoch = c.epoch

    def device_bytes(self) -> int:
        """HBM footprint of the mirror — what is ACTUALLY resident on
        device: 0 before the first upload and after the buffers were
        deleted (the host-side ``c.storage`` numpy array is not HBM)."""
        total = 0
        for buf in (self._dev_table, self._dev_slots):
            if buf is not None and not buf.is_deleted():
                total += buf.nbytes
        return total

    # -- reads ---------------------------------------------------------------
    def fetch(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64)
        c = self.cache
        if c is None or not c.capacity:
            # nothing resident on device — same contract as the host plane
            return super().fetch(ids)
        with self._locked():
            return self._fetch_locked(ids, c)

    def _fetch_locked(self, ids: np.ndarray, c: FeatureCache) -> np.ndarray:
        from repro.kernels.gather.ops import cache_gather
        self._ensure_synced()
        n = len(ids)
        out = np.empty((n, self.graph.feat_dim), np.float32)
        # the host-side device_map is bit-identical to the synced _dev_slots
        # mirror (both under this lock), so the miss set is known BEFORE the
        # device gather completes — that is what lets the host-store gather
        # for misses overlap the device gather of resident rows.  The slot
        # translation rides the SAME read (one map lookup, two uses): the
        # kernel receives the slots directly instead of re-deriving them
        # from the _dev_slots mirror with a device-side take per chunk
        slots_np = c.device_map[ids]
        miss = slots_np < 0
        pending = []                     # (offset, rows_on_device) per chunk
        for a in range(0, n, GATHER_CHUNK_ROWS):
            m = len(slots_np[a:a + GATHER_CHUNK_ROWS])
            mp = min(_bucket(m), GATHER_CHUNK_ROWS)
            # pad slots resolve to -1 (a miss) — zero rows, sliced off below
            pad = np.full(mp, -1, dtype=np.int32)
            pad[:m] = slots_np[a:a + m]
            rows, _ = cache_gather(pad, self._dev_table,
                                   use_pallas=self.use_pallas)
            # jax dispatch is async: don't block on the result yet
            pending.append((a, m, rows))
        # double-buffered miss path: gather missed rows from the host
        # store while the device works through the resident-row gathers
        miss_ids = ids[miss]
        host_rows = self.graph.features[miss_ids] if len(miss_ids) else None
        self.gather_dispatches += len(pending)
        self.gather_rows += n
        for a, m, rows in pending:
            out[a:a + m] = np.asarray(rows)[:m]      # blocks per chunk
        if len(miss_ids):
            out[miss] = host_rows
        # one accounting implementation for both planes (stats-exactness
        # is a tested invariant); a FIFO insert bumps version → re-sync
        c.account_fetch(~miss, miss_ids)
        return out

    def gather_aggregate(self, ids: np.ndarray, neigh_idx: np.ndarray,
                         mode: str = "mean"):
        """Fused layer-0 read against the device mirror: resident rows are
        addressed by cache slot (no batch feature tensor materializes on
        the kernel path), misses ride the host-gathered ``aux`` sideband.
        Bit-exact with the host plane: both resolve the same row values,
        then run the same aggregation."""
        ids = np.asarray(ids, dtype=np.int64)
        c = self.cache
        if c is None or not c.capacity:
            return super().gather_aggregate(ids, neigh_idx, mode=mode)
        with self._locked():
            self._ensure_synced()
            slots = c.device_map[ids]
            hit = slots >= 0
            miss_ids = ids[~hit]
            enc = np.empty(len(ids), np.int32)
            enc[hit] = slots[hit]
            enc[~hit] = -np.arange(1, len(miss_ids) + 1, dtype=np.int32)
            aux = (self.graph.features[miss_ids] if len(miss_ids)
                   else np.zeros((0, self.graph.feat_dim), np.float32))
            self.gather_dispatches += 1
            self.gather_rows += len(ids)
            out = _run_fused(enc, neigh_idx, self._dev_table, aux,
                             use_pallas=self.use_pallas, mode=mode)
            # same accounting seam as _fetch_locked (stats-exact invariant)
            c.account_fetch(hit, miss_ids)
        return out

    def fused_inputs(self, ids: np.ndarray, cap: int):
        """Device twin of the host ``fused_inputs``: resident rows are
        encoded as cache-table slots (``enc >= 0`` — ZERO feature bytes
        move for them), misses are scattered into a persistent per-cap
        device sideband through the donated ``_scatter`` path, so
        per-batch feature traffic is O(miss rows), never O(cap).  The
        returned ``table`` is the live device mirror — (capacity+pad, F)
        is a fixed shape, so every batch hits the one jitted step
        signature.

        The consuming train step must be serialized (the trainers block
        on ``float(loss)`` per step) — the sideband buffer is donated on
        the NEXT batch's scatter, which must not race an in-flight step."""
        ids = np.asarray(ids, dtype=np.int64)
        c = self.cache
        if c is None or not c.capacity:
            return super().fused_inputs(ids, cap)
        import jax
        import jax.numpy as jnp
        n = len(ids)
        if n > cap:
            raise ValueError(f"{n} input ids exceed level cap {cap}")
        with self._locked():
            self._ensure_synced()
            slots = c.device_map[ids]
            hit = slots >= 0
            miss_ids = ids[~hit]
            enc = np.full(cap, -1, np.int32)
            enc[:n][hit] = slots[hit]
            enc[:n][~hit] = -np.arange(1, len(miss_ids) + 1, dtype=np.int32)
            aux = self._aux_bufs.get(cap)
            if aux is None:
                aux = jax.device_put(
                    jnp.zeros((cap, self.graph.feat_dim), jnp.float32),
                    self.device)
            # pow2-padded miss scatter with out-of-range pad indices
            # (dropped — padded vals rows never land in the buffer), same
            # discipline as the mirror sync; m == 0 rides the minimal
            # bucket so EVERY batch is exactly one packing dispatch
            m = len(miss_ids)
            p = min(_bucket(max(m, 1)), cap)
            idx = np.full(p, cap, np.int32)
            idx[:m] = np.arange(m, dtype=np.int32)
            vals = np.empty((p, self.graph.feat_dim), np.float32)
            if m:
                vals[:m] = self.graph.features[miss_ids]
            enc_dev, aux = _fused_pack(aux, idx, vals, enc)
            self._aux_bufs[cap] = aux
            self.gather_dispatches += 1
            self.gather_rows += n
            c.account_fetch(hit, miss_ids)
            return enc_dev, aux, self._dev_table

    def fill_rows(self, ids: np.ndarray, rows: np.ndarray):
        with self._locked():
            super().fill_rows(ids, rows)

    def _on_store_update(self, ids: np.ndarray, rows: np.ndarray):
        with self._locked():
            super()._on_store_update(ids, rows)

    def resize(self, volume_mb: float, keep_residents: bool = True):
        with self._locked():
            super().resize(volume_mb, keep_residents=keep_residents)


def make_feature_plane(graph: Graph, cache: Optional[FeatureCache],
                       sampling_device: str = "cpu",
                       device=None) -> FeaturePlane:
    """Backend factory for the batch-generation gather stage.

    ``cpu`` → ``HostFeaturePlane``; ``device`` → ``DeviceFeaturePlane``
    mirrored on ``device`` (default: the first device); ``auto`` probes
    ``jax.devices()`` and picks the device plane only when a TPU is
    attached.
    """
    if sampling_device == "auto":
        import jax
        platform = jax.devices()[0].platform
        sampling_device = "device" if platform == "tpu" else "cpu"
        log.info("sampling_device=auto: %s feature plane on a %s backend",
                 sampling_device, platform)
    if sampling_device == "device":
        return DeviceFeaturePlane(graph, cache, device=device)
    if sampling_device in ("cpu", "host"):
        return HostFeaturePlane(graph, cache)
    raise ValueError(f"unknown sampling_device: {sampling_device!r} "
                     f"(expected cpu | device | auto)")
