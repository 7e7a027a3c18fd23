"""Pallas kernel: masked neighbor aggregation (the per-hop GNN hot-spot).

TPU adaptation of the CSR SpMM the GPU frameworks use: the sampler's
fixed-fanout padded blocks turn aggregation into a dense masked gather —
grid (dst_blocks,), each step handed its (rows_per_block, fanout) block
of neighbor indices in SMEM, one accumulator per dst row.  -1 indices
are padding (masked out).  The source buffer is one whole-array block,
staged into VMEM once per call.

Three aggregation families behind one kernel (models/gnn.py's fused
per-hop path): ``mean`` (GraphSAGE/GCN), ``sum`` (GIN) and weighted sum
(GAT — per-edge attention weights ride along as an SMEM block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import COMPILER_PARAMS


def _agg_kernel(idx_ref, h_ref, *rest, rows_per_block: int, fanout: int,
                mode: str, weighted: bool):
    w_ref, out_ref = rest if weighted else (None, rest[0])
    for r in range(rows_per_block):                 # static row unroll
        acc = jnp.zeros((1, out_ref.shape[-1]), jnp.float32)
        cnt = jnp.zeros((), jnp.float32)
        for f in range(fanout):                     # static fanout unroll
            idx = idx_ref[r, f]
            valid = idx >= 0
            row = h_ref[pl.ds(jnp.maximum(idx, 0), 1), :].astype(jnp.float32)
            if weighted:
                row = w_ref[r, f] * row
            acc = acc + jnp.where(valid, row, 0.0)
            cnt = cnt + jnp.where(valid, 1.0, 0.0)
        agg = acc / jnp.maximum(cnt, 1.0) if mode == "mean" else acc
        out_ref[r:r + 1, :] = agg.astype(out_ref.dtype)


def neighbor_agg_pallas(neigh_idx: jnp.ndarray, h_src: jnp.ndarray, *,
                        interpret: bool, mode: str = "mean", weights=None,
                        rows_per_block: int = 8):
    """neigh_idx (Nd, fanout) int32 (−1 pad); h_src (Ns, F);
    weights (Nd, fanout) float or None → (Nd, F)."""
    Nd, fanout = neigh_idx.shape
    Ns, F = h_src.shape
    assert Nd % rows_per_block == 0
    smem_block = pl.BlockSpec((rows_per_block, fanout), lambda i: (i, 0),
                              memory_space=pltpu.SMEM)
    in_specs = [smem_block, pl.BlockSpec((Ns, F), lambda i: (0, 0))]
    args = [neigh_idx, h_src]
    if weights is not None:
        in_specs.append(smem_block)
        args.append(weights.astype(jnp.float32))
    kernel = functools.partial(_agg_kernel, rows_per_block=rows_per_block,
                               fanout=fanout, mode=mode,
                               weighted=weights is not None)
    return pl.pallas_call(
        kernel,
        grid=(Nd // rows_per_block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows_per_block, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Nd, F), h_src.dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="neighbor_agg",
    )(*args)
