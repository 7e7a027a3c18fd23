"""jit wrapper: pad ids/dst rows to the id block through the memoized pad
plan, dispatch kernel/ref.

Contract (shared with ref.py, regression-tested in tests/test_fused_agg.py):
``enc (Ns,) int32`` encodes where each input id's feature row lives —
``enc[i] >= 0`` is a cache-table slot, ``enc[i] < 0`` is row ``-enc[i]-1``
of the ``aux`` sideband (host-gathered misses; must have ≥ 1 row).
``neigh_idx (Nd, fanout)`` indexes the input ids (−1 = pad), the dst ids
being the prefix of the input ids (``Nd ≤ Ns``).  Returns
``(h_dst (Nd, F), agg (Nd, F))`` — the self rows and the masked neighbor
aggregate (``mode``: ``mean`` for GraphSAGE/GCN layer 0, ``sum`` for GIN)
— without ever materializing the (Ns, F) batch tensor on the kernel
path.  Padded dst rows are sliced away; padded enc entries resolve to
``aux[0]`` and are never referenced by a real dst row.  The Pallas path
stages table and sideband whole in VMEM and ``enc`` in SMEM, and refuses
them past their budgets (kernels/platform.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_gather_agg.kernel import gather_aggregate_pallas
from repro.kernels.fused_gather_agg.ref import gather_aggregate_ref
from repro.kernels.pad_plan import pad_plan, row_plan
from repro.kernels.platform import (PLANE_REMEDY, check_staged,
                                    pallas_on_platform)


def _id_plan(Nd: int, Ns: int):
    """(padded Nd, padded Ns): both block multiples, Ns ≥ Nd."""
    def compute():
        ndp = row_plan(Nd)
        return ndp, max(row_plan(Ns), ndp)
    return pad_plan("fused_ids", (Nd, Ns), compute)


@functools.partial(jax.jit, static_argnames=("mode", "use_pallas"))
def gather_aggregate(enc, neigh_idx, cache, aux, mode: str = "mean",
                     use_pallas: bool = True):
    Nd, fanout = neigh_idx.shape
    Ns = enc.shape[0]
    ndp, nsp = _id_plan(Nd, Ns)
    enc_p = jnp.pad(enc.astype(jnp.int32), (0, nsp - Ns),
                    constant_values=-1)
    idx_p = jnp.pad(neigh_idx.astype(jnp.int32), ((0, ndp - Nd), (0, 0)),
                    constant_values=-1)
    if use_pallas:
        check_staged("gather_aggregate", vmem=[cache, aux], smem=[enc_p],
                     remedy=PLANE_REMEDY + ", or a smaller batch or fanout")
        h, a = pallas_on_platform(
            functools.partial(gather_aggregate_pallas, mode=mode),
            enc_p, idx_p, cache, aux)
    else:
        h, a = gather_aggregate_ref(enc_p, idx_p, cache, aux, mode=mode)
    return h[:Nd].astype(cache.dtype), a[:Nd].astype(cache.dtype)
