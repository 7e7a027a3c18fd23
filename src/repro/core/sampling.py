"""Locality-aware graph sampling (paper §III-A, Algo. 2).

Core mechanism: Efraimidis–Spirakis weighted reservoir sampling — key
k_j = u_j^{1/w_j}, keep the top-m keys.  Cached vertices get weight γ
(bias rate), uncached weight 1, so sampling is biased toward cache hits.

Two implementations with identical distribution:
  * ``reservoir_sample_ref``  — the paper's sequential Algo. 2 (oracle)
  * ``es_sample``             — vectorized keys + top-m (TPU-native shape;
    the Pallas kernel in kernels/reservoir mirrors this formulation)

``NeighborSampler`` builds multi-hop GraphSAGE-style blocks with fixed
fanout padding (static shapes → jit-friendly training batches).  A hub
row (degree far above its fanout) under weights with declared bounds
draws its picks by rejection instead, in O(fanout) work: the same
successive weighted sampling without replacement that ES keys give.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.spans import span
from repro.graph.storage import Graph

# A row takes the rejection path when its degree d exceeds
# REJECT_FACTOR · fanout · (w_max / w_min).  A pick there costs at most
# (w_max / w_min) · d / (d − fanout) proposals, each a random gather of
# one id and its weight, against ES's d keys per row (repeat, gather,
# weight, random, log and a padded argpartition each).  Timed on a CPU on
# rows of one degree spread over a 512 MB neighbour array (fanout 5, 10,
# 15; γ 1, 2), ES was cheaper below d ≈ 1.5–3 · fanout · (w_max / w_min)
# and rejection 1.5–3.4× cheaper per row above 3; 2.5 sits between.
REJECT_FACTOR = 2.5


def reservoir_sample_ref(neighbors: np.ndarray, weights: np.ndarray, m: int,
                         rng: np.random.Generator) -> np.ndarray:
    """Algo. 2 verbatim: sequential weighted reservoir sampling."""
    if len(neighbors) <= m:
        return neighbors.copy()
    res_items = list(neighbors[:m])
    keys = list(rng.random(m) ** (1.0 / weights[:m]))
    for j in range(m, len(neighbors)):
        k_j = rng.random() ** (1.0 / weights[j])
        t = int(np.argmin(keys))
        if k_j > keys[t]:
            res_items[t] = neighbors[j]
            keys[t] = k_j
    return np.asarray(res_items, dtype=neighbors.dtype)


def es_keys(weights: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Efraimidis–Spirakis keys u^{1/w} (log-space for stability)."""
    u = rng.random(weights.shape)
    return np.log(np.maximum(u, 1e-300)) / np.maximum(weights, 1e-12)


def es_sample(neighbors: np.ndarray, weights: np.ndarray, m: int,
              rng: np.random.Generator) -> np.ndarray:
    """Vectorized top-m by ES keys — same distribution as Algo. 2."""
    if len(neighbors) <= m:
        return neighbors.copy()
    keys = es_keys(weights, rng)
    top = np.argpartition(-keys, m - 1)[:m]
    return neighbors[top]


@dataclass
class Block:
    """One hop: bipartite (src → dst) with fixed-fanout padding.

    ``neigh_idx[i, f]`` indexes ``src_ids``; -1 = padded slot."""
    src_ids: np.ndarray      # (n_src,) global node ids (dst ids are a prefix)
    dst_ids: np.ndarray      # (n_dst,)
    neigh_idx: np.ndarray    # (n_dst, fanout) int32, -1 padded


@dataclass
class MiniBatch:
    blocks: List[Block]          # input-hop first
    input_ids: np.ndarray        # node ids needing features (== blocks[0].src_ids)
    seeds: np.ndarray            # (batch,)
    labels: np.ndarray           # (batch,)
    features: Optional[np.ndarray] = None   # filled by batch generation
    # (stays None under GNNConfig.fused_gather_agg — the trainer resolves
    # the input hop at step time through FeaturePlane.fused_inputs)
    # graph topology version the batch was sampled at (dynamic graphs:
    # lets downstream consumers detect batches drawn before a mutation)
    topology_version: int = -1

    def num_input_nodes(self) -> int:
        return len(self.input_ids)


class NeighborSampler:
    """Multi-hop locality-aware sampler.

    ``weight_fn(ids) -> weights`` implements the bias: γ for cached ids,
    1 otherwise (see core/locality.py).  ``use_reference=True`` switches to
    the sequential Algo. 2 oracle (tests)."""

    def __init__(self, graph: Graph, fanouts: Sequence[int],
                 weight_fn: Optional[Callable[[np.ndarray], np.ndarray]] = None,
                 seed: int = 0, use_reference: bool = False):
        self.g = graph
        self.fanouts = tuple(fanouts)
        self.weight_fn = weight_fn
        self.rng = np.random.default_rng(seed)
        self.use_reference = use_reference

    def _weight_bounds(self) -> Optional[Tuple[float, float]]:
        """(w_min, w_max) the weight function declares; None without."""
        if self.weight_fn is None:
            return 1.0, 1.0
        lo = getattr(self.weight_fn, "min_weight", None)
        hi = getattr(self.weight_fn, "max_weight", None)
        return None if lo is None or hi is None else (float(lo), float(hi))

    def _sample_one_hop(self, dst_ids: np.ndarray,
                        fanout: int) -> Tuple[np.ndarray, int, int]:
        """Returns sampled (n_dst, fanout) global ids with -1 pad, the
        number of rows drawn by rejection and the proposals they took."""
        g = self.g
        # both paths read through the merged base+overlay view, so edge
        # mutations are visible to the very next hop; for a frozen graph
        # adj() returns the base arrays untouched (bit-exact with the old
        # direct reads)
        indptr, indices = g.adj()
        out = -np.ones((len(dst_ids), fanout), dtype=np.int64)
        if self.use_reference:
            for i, v in enumerate(dst_ids):
                nb = indices[indptr[v]:indptr[v + 1]]
                if len(nb) == 0:
                    continue
                w = (np.ones(len(nb)) if self.weight_fn is None
                     else self.weight_fn(nb))
                picked = reservoir_sample_ref(nb, w, min(fanout, len(nb)),
                                              self.rng)
                out[i, :len(picked)] = picked
            return out, 0, 0
        starts = indptr[dst_ids]
        sizes = (indptr[dst_ids + 1] - starts).astype(np.int64)
        # hub rows: rejection, O(fanout) per row; the rest below by ES
        hub = np.zeros(len(sizes), bool)
        bounds = self._weight_bounds()
        if bounds is not None and bounds[0] > 0:
            hub = sizes > REJECT_FACTOR * fanout * bounds[1] / bounds[0]
        proposals = 0
        if hub.any():
            rows = np.where(hub)[0]
            out[rows], proposals = self._reject_picks(
                indices, starts[rows], sizes[rows], fanout, *bounds)
            sizes = np.where(hub, 0, sizes)
        # vectorized ES: one key computation over all edges of the other
        # rows, then BUCKETED batched top-m (rows grouped by padded width) —
        # all work is large numpy ops that release the GIL, so sampler
        # threads scale
        # (the host-side twin of the kernels/reservoir TPU formulation).
        total = int(sizes.sum())
        if total == 0:
            return out, int(hub.sum()), proposals
        row_start = np.cumsum(sizes) - sizes
        offs = np.repeat(starts, sizes) + (np.arange(total)
                                           - np.repeat(row_start, sizes))
        nb_all = indices[offs]

        # rows with ≤ fanout neighbors: take everything (no keys needed)
        small = sizes <= fanout
        if small.any():
            rs = np.where(small)[0]
            w = int(sizes[rs].max()) if len(rs) else 0
            if w > 0:
                col = np.arange(w)
                valid = col[None, :] < sizes[rs, None]
                src = row_start[rs, None] + np.minimum(col[None, :],
                                                       sizes[rs, None] - 1)
                block = nb_all[src]
                row_idx = np.broadcast_to(rs[:, None], valid.shape)
                col_idx = np.broadcast_to(col[None, :], valid.shape)
                out[row_idx[valid], col_idx[valid]] = block[valid]

        big = ~small & (sizes > 0)
        if big.any():
            w_all = (np.ones(total) if self.weight_fn is None
                     else self.weight_fn(nb_all))
            keys = es_keys(w_all, self.rng)
            rows = np.where(big)[0]
            widths = 1 << np.ceil(np.log2(sizes[rows])).astype(int)
            for w in np.unique(widths):
                rs = rows[widths == w]
                col = np.arange(w)
                valid = col[None, :] < sizes[rs, None]
                src = row_start[rs, None] + np.minimum(col[None, :],
                                                       sizes[rs, None] - 1)
                km = np.where(valid, keys[src], -np.inf)
                top = np.argpartition(-km, fanout - 1, axis=1)[:, :fanout]
                out[rs[:, None], np.arange(fanout)[None, :]] = (
                    nb_all[np.take_along_axis(src, top, axis=1)])
        return out, int(hub.sum()), proposals

    def _reject_picks(self, indices: np.ndarray, starts: np.ndarray,
                      sizes: np.ndarray, fanout: int, w_min: float,
                      w_max: float) -> Tuple[np.ndarray, int]:
        """``fanout`` picks from each row (all of degree > fanout) by
        rejection: each round, every row still short proposes one uniform
        position of its own, accepted with probability w/w_max unless the
        row already took it.  Each accepted pick is then drawn ∝ w among
        the positions not yet taken, which is the successive sampling ES
        keys give.  Rows work on positions, not ids, so a multi-edge counts
        as two items, as under ES.  Returns the (n, fanout) ids and the
        number of proposals."""
        n = len(sizes)
        taken = np.full((n, fanout), -1, dtype=np.int64)   # positions
        filled = np.zeros(n, dtype=np.int64)
        act = np.arange(n)
        proposals = 0
        while len(act):
            pos = (self.rng.random(len(act)) * sizes[act]).astype(np.int64)
            proposals += len(act)
            if w_max > w_min:
                w = self.weight_fn(indices[starts[act] + pos])
                keep = self.rng.random(len(act)) * w_max < w
                act, pos = act[keep], pos[keep]
            had, k = taken[act], filled[act]
            ok = np.ones(len(act), dtype=bool)
            for j in range(int(k.max()) if len(k) else 0):
                ok &= had[:, j] != pos
            taken[act[ok], k[ok]] = pos[ok]
            filled[act[ok]] += 1
            act = np.flatnonzero(filled < fanout)
        return indices[starts[:, None] + taken], proposals

    def sample(self, seeds: np.ndarray) -> MiniBatch:
        seeds = np.asarray(seeds, dtype=np.int64)
        blocks: List[Block] = []
        dst = seeds
        for fanout in self.fanouts:           # hop 1 = nearest to output
            with span("sampler.hop", rows=len(dst)) as sp:
                nbrs, hubs, proposals = self._sample_one_hop(dst, fanout)
                sp.set_metadata(reject_rows=hubs, proposals=proposals,
                                picks=hubs * fanout)
            # src set = dst ∪ sampled, with dst occupying the prefix positions
            valid = nbrs >= 0
            flat = nbrs[valid]
            src_sorted, inv = np.unique(np.concatenate([dst, flat]),
                                        return_inverse=True)
            dst_pos = inv[:len(dst)]                      # dst are unique
            in_dst = np.zeros(len(src_sorted), bool)
            in_dst[dst_pos] = True
            order = np.concatenate([dst_pos, np.where(~in_dst)[0]])
            src_ids = src_sorted[order]
            new_pos = np.empty(len(src_sorted), np.int32)
            new_pos[order] = np.arange(len(src_sorted), dtype=np.int32)
            neigh_idx = -np.ones_like(nbrs, dtype=np.int32)
            if valid.any():
                neigh_idx[valid] = new_pos[np.searchsorted(src_sorted, flat)]
            blocks.append(Block(src_ids=src_ids.astype(np.int64),
                                dst_ids=dst.astype(np.int64),
                                neigh_idx=neigh_idx))
            dst = src_ids
        blocks.reverse()                      # input hop first
        return MiniBatch(blocks=blocks, input_ids=blocks[0].src_ids,
                         seeds=seeds, labels=self.g.labels[seeds],
                         topology_version=self.g.topology_version)


def seed_loader(graph: Graph, batch_size: int, seed: int = 0,
                mask: Optional[np.ndarray] = None):
    """Iterate shuffled train-seed batches (drop last partial)."""
    ids = np.where(graph.train_mask if mask is None else mask)[0]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(ids)
    for i in range(0, len(perm) - batch_size + 1, batch_size):
        yield perm[i:i + batch_size].astype(np.int64)
