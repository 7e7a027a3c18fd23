"""Explicit collectives built on shard_map: flash-decoding attention combine
and quantized reductions (compression lives in train/compression.py).

These are the hand-written alternatives to GSPMD's automatic choices —
used when the automatic partitioner picks a bad schedule (e.g. gathering a
sequence-sharded KV cache instead of combining partial softmaxes).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _partial_attend(q, k, v, mask):
    """Local attention over this shard's time slice.

    q (B,H,Dh); k/v (B,Tl,H,Dh); mask (B,Tl) True=valid.
    Returns (o (B,H,Dh) UNNORMALIZED numerator at local max, m (B,H) local
    max, denom (B,H) local sum of exp)."""
    scores = jnp.einsum("bhe,bthe->bht", q, k).astype(jnp.float32)
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)                        # (B,H)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(mask[:, None, :], p, 0.0)
    denom = jnp.sum(p, axis=-1)                         # (B,H)
    o = jnp.einsum("bht,bthe->bhe", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m, denom


def flash_decode_attention(mesh: Mesh, axis: str = "model"):
    """Sequence-sharded single-token attention with psum softmax combine.

    Inputs (global): q (B,H,Dh) replicated over ``axis``; cache_k/v
    (B,T,H,Dh) sharded on T over ``axis``; pos (B,) replicated.
    Output: (B,H,Dh) replicated — each shard attends over its T-slice and
    the partial (o·softmax-weight, lse) pairs combine with one psum instead
    of all-gathering the cache (bytes: B·H·Dh vs B·T·H·Dh/axis).
    """
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def local(q, k, v, pos):
        Tl = k.shape[1]
        shard = jax.lax.axis_index(axis)
        base = shard * Tl
        mask = (base + jnp.arange(Tl))[None, :] <= pos[:, None]
        o, m, denom = _partial_attend(q, k, v, mask)
        g_max = jax.lax.pmax(m, axis)                   # (B,H) global max
        w = jnp.exp(m - g_max)                          # rescale to global max
        num = jax.lax.psum(o * w[..., None], axis)
        den = jax.lax.psum(denom * w, axis)
        return (num / jnp.maximum(den[..., None], 1e-30)).astype(v.dtype)

    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None), P())
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)


def grad_allreduce(mesh, axis: str = "part"):
    """Mean-all-reduce over per-partition gradient pytrees (data-parallel
    GNN scale-out, core/multipart.py).

    Returns ``fn(trees) -> tree`` averaging a list of identically-structured
    gradient pytrees, one per partition.  On a real ``Mesh`` tree p lives
    on mesh device p: each leaf becomes one global array sharded over
    ``axis`` without leaving its device, is reduced with a shard_map psum
    (the collective that runs on hardware), and the mean comes back on
    mesh device 0.  On a ``HostSimMesh`` (CI: fewer devices than
    partitions) the same reduction happens as host-side tree arithmetic —
    bitwise the same mean, no device topology required.
    """
    from repro.launch.mesh import HostSimMesh

    if isinstance(mesh, HostSimMesh) or mesh is None:
        def host_mean(trees):
            n = float(len(trees))
            if len(trees) == 1:
                return trees[0]
            return jax.tree.map(lambda *xs: sum(xs) / n, *trees)
        return host_mean

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, P(axis))

    def local(x):
        return jax.lax.psum(x, axis) / axis_size

    # built ONCE per grad_allreduce call; jit caches per gradient-tree
    # structure, so the per-step cost is a single dispatch, not a retrace
    reduce_leaf = jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                out_specs=P(axis), check_vma=False)
    tree_mean = jax.jit(lambda stacked: jax.tree.map(reduce_leaf, stacked))

    def stack_leaf(*xs):
        shards = [jax.device_put(x, d)[None] for x, d in zip(xs, devices)]
        return jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape, sharding, shards)

    def first_row(s):
        # every shard holds the mean after the psum; keep device 0's copy
        shard = next(sh for sh in s.addressable_shards
                     if sh.device == devices[0])
        return shard.data[0]

    def mesh_mean(trees):
        if len(trees) != axis_size:
            raise ValueError(f"got {len(trees)} gradient trees for a "
                             f"{axis_size}-way '{axis}' mesh axis")
        stacked = jax.tree.map(stack_leaf, *trees)
        return jax.tree.map(first_row, tree_mean(stacked))

    return mesh_mean


def halo_all_to_all(mesh, axis: str = "part"):
    """Bounded halo-feature exchange over the partition mesh.

    Returns ``fn(plan, part_feats) -> (halo_feats, volume_bytes)`` where
    ``part_feats[p]`` are partition p's OWNED feature rows in local order
    and ``halo_feats[p]`` are the rows for ``plan.halo_sets[p]`` in halo
    order — every row is owned by another partition, so all of them cross
    a boundary (``volume_bytes`` counts exactly that traffic, the HitGNN
    inter-device term the ``halo_budget`` knob caps).

    On a real ``Mesh`` (one device per partition) the rows move through a
    shard_map ``jax.lax.all_to_all`` over per-pair send buffers padded to
    the largest pair; on a ``HostSimMesh`` (CI: fewer devices than
    partitions) the same routing runs as host-side gathers — bitwise the
    same rows, no device topology required.
    """
    import numpy as np

    from repro.launch.mesh import HostSimMesh

    def _routing(plan):
        """Global→local index map plus, per (src q → dst p) pair, the rows
        q sends (q-local ids) and where p scatters them (halo positions)."""
        parts = plan.parts
        loc = np.zeros(len(plan.owner), np.int64)
        for ns in plan.node_sets:
            loc[ns] = np.arange(len(ns))
        send = [[None] * parts for _ in range(parts)]   # send[q][p]
        put = [[None] * parts for _ in range(parts)]    # put[p][q]
        for p, hs in enumerate(plan.halo_sets):
            owners = plan.owner[hs] if len(hs) else np.zeros(0, np.int32)
            for q in range(parts):
                pos = np.where(owners == q)[0]
                send[q][p] = loc[hs[pos]]
                put[p][q] = pos
        return send, put

    def _volume(plan, feat_dim: int) -> int:
        return plan.halo_rows * feat_dim * 4

    if isinstance(mesh, HostSimMesh) or mesh is None:
        def host_exchange(plan, part_feats):
            send, put = _routing(plan)
            halo_feats = []
            for p, hs in enumerate(plan.halo_sets):
                rows = np.zeros((len(hs), part_feats[p].shape[1]), np.float32)
                for q in range(plan.parts):
                    if len(put[p][q]):
                        rows[put[p][q]] = part_feats[q][send[q][p]]
                halo_feats.append(rows)
            return halo_feats, _volume(plan, part_feats[0].shape[1])
        return host_exchange

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def mesh_exchange(plan, part_feats):
        if plan.parts != axis_size:
            raise ValueError(f"plan has {plan.parts} partitions for a "
                             f"{axis_size}-way '{axis}' mesh axis")
        send, put = _routing(plan)
        feat_dim = part_feats[0].shape[1]
        pad = max((len(send[q][p]) for q in range(plan.parts)
                   for p in range(plan.parts)), default=0)
        if pad == 0:
            return ([np.zeros((0, feat_dim), np.float32)
                     for _ in range(plan.parts)], 0)
        # send_buf[q] : (parts, pad, F) — block p = rows q ships to p
        bufs = []
        for q in range(plan.parts):
            buf = np.zeros((plan.parts, pad, feat_dim), np.float32)
            for p in range(plan.parts):
                rows = send[q][p]
                buf[p, :len(rows)] = part_feats[q][rows]
            bufs.append(buf)
        # (parts, parts, pad, F): block q goes straight to device q
        stacked = jax.device_put(np.stack(bufs), NamedSharding(mesh, P(axis)))

        def local(x):                                 # x: (1, parts, pad, F)
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0)

        recv = jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)(stacked)
        # shard p's local (parts, 1, pad, F) blocks concatenate on axis 0
        recv = np.asarray(recv).reshape(plan.parts, plan.parts, pad,
                                        feat_dim)   # recv[p][q] = send[q][p]
        halo_feats = []
        for p, hs in enumerate(plan.halo_sets):
            rows = np.zeros((len(hs), feat_dim), np.float32)
            for q in range(plan.parts):
                if len(put[p][q]):
                    rows[put[p][q]] = recv[p, q, :len(put[p][q])]
            halo_feats.append(rows)
        return halo_feats, _volume(plan, feat_dim)

    return mesh_exchange


def quantized_allreduce_bytes(shape, n_devices: int, bits: int = 8) -> float:
    """Analytic DCN volume of a compressed ring all-reduce (roofline helper)."""
    import numpy as np
    elems = float(np.prod(shape))
    payload = elems * bits / 8
    return 2.0 * payload * (n_devices - 1) / n_devices
