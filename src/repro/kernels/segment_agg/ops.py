"""jit wrapper: pad dst rows (memoized pad plan), dispatch kernel/ref.

``neighbor_agg`` is the per-hop fused aggregation entry the GNN layers
call (models/gnn.py, ``fused=True``): the previous layer's output buffer
is consumed in place — the (Nd, fanout, D) gathered-neighbor tensor of
the unfused path never materializes on the kernel path.  ``mode`` picks
the aggregation family (``mean`` — GraphSAGE/GCN; ``sum`` — GIN);
``weights`` (GAT attention, (Nd, fanout)) rides along for the weighted
sum.  With ``use_pallas=False`` the jitted pure-jnp oracle runs instead
(it is differentiable, which the train step requires — the Pallas path
is forward-only today).  The Pallas path stages the whole source buffer
in VMEM and refuses one past the budget (kernels/platform.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pad_plan import row_plan
from repro.kernels.platform import check_staged, pallas_on_platform
from repro.kernels.segment_agg.kernel import neighbor_agg_pallas
from repro.kernels.segment_agg.ref import neighbor_agg_ref


@functools.partial(jax.jit, static_argnames=("mode", "use_pallas"))
def neighbor_agg(neigh_idx, h_src, mode: str = "mean", weights=None,
                 use_pallas: bool = True):
    if weights is not None and mode != "sum":
        # one contract across backends: attention weights are already
        # normalized, so the weighted family is the SUM (see ref.py)
        raise ValueError("per-edge weights imply mode='sum'")
    Nd, fanout = neigh_idx.shape
    ndp = row_plan(Nd)
    idx_p = jnp.pad(neigh_idx.astype(jnp.int32), ((0, ndp - Nd), (0, 0)),
                    constant_values=-1)
    w_p = (None if weights is None
           else jnp.pad(weights, ((0, ndp - Nd), (0, 0))))
    if use_pallas:
        check_staged("neighbor_agg", vmem=[h_src],
                     remedy="pass fewer source rows, or use_pallas=False")

        def kernel(idx, h, w, *, interpret):
            return neighbor_agg_pallas(idx, h, mode=mode, weights=w,
                                       interpret=interpret)
        out = pallas_on_platform(kernel, idx_p, h_src, w_p)
    else:
        out = neighbor_agg_ref(idx_p, h_src, mode=mode, weights=w_p)
    return out[:Nd]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def neighbor_mean(neigh_idx, h_src, use_pallas: bool = True):
    return neighbor_agg(neigh_idx, h_src, mode="mean", use_pallas=use_pallas)
