"""Pallas kernel: fused cache gather + neighbor aggregation.

The unfused hot path materializes the full sampled-feature batch tensor
(kernels/gather) and immediately reduces it (kernels/segment_agg) — for a
fanout-k layer that round-trips k× the aggregated volume through HBM.
This kernel chains the two: neighbor rows are resolved straight out of the
cache table (or the host-filled miss sideband) and folded into the
per-dst accumulator, so sampled neighbor features never exist as a
separate batch tensor.

Row addressing uses an *encoded slot* per input id:

  ``enc[i] >= 0`` → the row lives in the cache table at slot ``enc[i]``
  ``enc[i] <  0`` → the row is ``aux[-enc[i] - 1]`` (host-gathered miss)

Grid: (dst_blocks,).  ``enc`` is scalar-prefetched into SMEM, and each
step is handed its (rows_per_block, fanout) block of neighbor indices in
SMEM.  Table and sideband are whole-array blocks, staged into VMEM once
per call.  Outputs both the dst-prefix rows (``h_dst``, the self term of
the SAGE layer) and the neighbor aggregate (``agg``) in one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import COMPILER_PARAMS


def _resolve(enc, cache_ref, aux_ref):
    """Load one feature row through the encoded slot (see module doc)."""
    crow = cache_ref[pl.ds(jnp.maximum(enc, 0), 1), :]
    arow = aux_ref[pl.ds(jnp.maximum(-enc - 1, 0), 1), :]
    return jnp.where(enc >= 0, crow, arow).astype(jnp.float32)


def _fused_kernel(enc_ref, idx_ref, cache_ref, aux_ref, hdst_ref, agg_ref, *,
                  rows_per_block: int, fanout: int, mode: str):
    base = pl.program_id(0) * rows_per_block        # enc_ref is unblocked
    for r in range(rows_per_block):                 # static row unroll
        # self term: the dst ids are the prefix of the input ids
        row = _resolve(enc_ref[base + r], cache_ref, aux_ref)
        hdst_ref[r:r + 1, :] = row.astype(hdst_ref.dtype)
        acc = jnp.zeros((1, agg_ref.shape[-1]), jnp.float32)
        cnt = jnp.zeros((), jnp.float32)
        for f in range(fanout):                     # static fanout unroll
            idx = idx_ref[r, f]
            valid = idx >= 0
            nrow = _resolve(enc_ref[jnp.maximum(idx, 0)], cache_ref, aux_ref)
            acc = acc + jnp.where(valid, nrow, 0.0)
            cnt = cnt + jnp.where(valid, 1.0, 0.0)
        agg = acc / jnp.maximum(cnt, 1.0) if mode == "mean" else acc
        agg_ref[r:r + 1, :] = agg.astype(agg_ref.dtype)


def gather_aggregate_pallas(enc: jnp.ndarray, neigh_idx: jnp.ndarray,
                            cache: jnp.ndarray, aux: jnp.ndarray, *,
                            interpret: bool, mode: str = "mean",
                            rows_per_block: int = 8):
    """enc (Ns,) int32; neigh_idx (Nd, fanout) int32 (−1 pad, values in
    [0, Ns)); cache (C, F); aux (Na, F) → (h_dst (Nd, F), agg (Nd, F));
    ``mode`` is ``mean`` (GraphSAGE/GCN) or ``sum`` (GIN)."""
    Ns = enc.shape[0]
    Nd, fanout = neigh_idx.shape
    C, F = cache.shape
    assert Nd % rows_per_block == 0 and Ns >= Nd
    kernel = functools.partial(_fused_kernel, rows_per_block=rows_per_block,
                               fanout=fanout, mode=mode)
    row_block = pl.BlockSpec((rows_per_block, F), lambda i, enc: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Nd // rows_per_block,),
        in_specs=[pl.BlockSpec((rows_per_block, fanout),
                               lambda i, enc: (i, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((C, F), lambda i, enc: (0, 0)),
                  pl.BlockSpec(aux.shape, lambda i, enc: (0, 0))],
        out_specs=[row_block, row_block],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Nd, F), cache.dtype),
                   jax.ShapeDtypeStruct((Nd, F), cache.dtype)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="gather_aggregate",
    )(enc, neigh_idx, cache, aux)
