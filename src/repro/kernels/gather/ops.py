"""jit wrapper: pad n to the id block, dispatch kernel/ref.

Contract (shared with ref.py, regression-tested in tests/test_kernels.py):
``slots (n,) int → (features (n, F) of ``cache.dtype``, miss (n,) int32)``
for ANY n ≥ 1 and ANY feature width F (e.g. the reddit twin's F=602).
Miss rows are zero; ``miss`` is derived from ``slots < 0`` on both paths.
Padded id rows are synthesized as misses and sliced away.  The Pallas
path stages the whole table in VMEM and the slots in SMEM, and refuses
either past its budget (kernels/platform.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gather.kernel import cache_gather_pallas
from repro.kernels.gather.ref import cache_gather_ref
from repro.kernels.pad_plan import row_plan
from repro.kernels.platform import (PLANE_REMEDY, check_staged,
                                    pallas_on_platform)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def cache_gather(slots, cache, use_pallas: bool = True):
    """slots (n,) int32 (−1 miss) → (features (n,F), miss (n,) int32)."""
    n = slots.shape[0]
    slots = slots.astype(jnp.int32)
    if use_pallas:
        # the kernel moves single 32-bit rows (Mosaic cannot slice one row
        # of a packed 16-bit tile at a dynamic offset): a narrower table is
        # widened per call, exactly — every production table is float32
        table = cache if cache.dtype.itemsize == 4 else cache.astype(
            jnp.float32)
        slots_p = jnp.pad(slots, (0, row_plan(n) - n), constant_values=-1)
        check_staged("cache_gather", vmem=[table], smem=[slots_p],
                     remedy=PLANE_REMEDY)
        out = pallas_on_platform(cache_gather_pallas, slots_p, table)[:n]
    else:
        out = cache_gather_ref(slots, cache)
    return out.astype(cache.dtype), (slots < 0).astype(jnp.int32)
