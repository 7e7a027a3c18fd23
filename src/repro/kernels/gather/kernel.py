"""Pallas kernel: device-map feature-cache gather.

Batch-generation hot loop on the device side: for each requested node id
the host passes its cache slot (scalar-prefetched into SMEM) and the
kernel copies the feature row out of the cache table into the output
batch buffer.  A miss (slot < 0) yields a zero row; the caller fills it
from the host feature store (the paper's PCIe path, overlapped by
pipeline mode 1/2) and already knows the miss set from the slots.

Grid: (id_blocks,).  The whole table is one block, staged into VMEM once
per call (a block equal to the full array is single-buffered); the slots
are scalar-prefetched so each row address is known before the block body
runs (the Pallas analogue of the paper's "device map for efficient
lookup").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import COMPILER_PARAMS


def _gather_kernel(slots_ref, cache_ref, out_ref, *, ids_per_block: int):
    base = pl.program_id(0) * ids_per_block         # slots_ref is unblocked
    for r in range(ids_per_block):                  # static unroll (8 rows)
        slot = slots_ref[base + r]
        row = cache_ref[pl.ds(jnp.maximum(slot, 0), 1), :]          # (1, F)
        out_ref[r:r + 1, :] = jnp.where(slot >= 0, row, jnp.zeros_like(row))


def cache_gather_pallas(slots: jnp.ndarray, cache: jnp.ndarray, *,
                        interpret: bool, ids_per_block: int = 8):
    """slots (n,) int32 (−1 = miss); cache (C, F) → out (n, F)."""
    n = slots.shape[0]
    C, F = cache.shape
    assert n % ids_per_block == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // ids_per_block,),
        in_specs=[pl.BlockSpec((C, F), lambda i, slots: (0, 0))],
        out_specs=pl.BlockSpec((ids_per_block, F), lambda i, slots: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, ids_per_block=ids_per_block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, F), cache.dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="cache_gather",
    )(slots, cache)
