"""Where a Pallas kernel runs compiled, and what it may stage in VMEM.

Every kernel's ``ops.py`` dispatches through ``pallas_on_platform``: the
choice is made when the program is lowered, for the platform it is
lowered for (``jax.lax.platform_dependent``).  A program lowered for a
TPU runs the compiled kernel and never the interpreter; on any other
backend the same call runs the Pallas interpreter, which is how the
kernels are tested on CPU hosts.  No caller passes an ``interpret`` flag.

The GNN kernels stage whole tables (the feature cache, a layer's source
buffer) as one VMEM block.  ``check_staged`` refuses, with a clear
error and on every platform, a shape the TPU compiler would refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import numpy as np

# a TPU v5e core has 128 MiB of VMEM and 1 MiB of SMEM.  The GNN kernels
# raise the compiler's scoped-VMEM limit (16 MiB by default) so a staged
# table fits, and keep the whole-array blocks they stage, in padded bytes,
# under VMEM_STAGED_BYTES; their scalar-prefetched index arrays stay
# under SMEM_PREFETCH_BYTES
VMEM_LIMIT_BYTES = 120 * 2**20
VMEM_STAGED_BYTES = 100 * 2**20
SMEM_PREFETCH_BYTES = 900 * 2**10
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
# what a feature plane's user changes when its cache table is over budget
PLANE_REMEDY = ("set a smaller cache volume (GNNConfig.cache_volume_mb, "
                "Θ), or sampling_device='cpu' to read features on the host")


def pallas_on_platform(kernel, *args):
    """``kernel(*args, interpret=...)``: compiled for a TPU, interpreted
    on every other platform."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))


def vmem_bytes(shape, dtype) -> int:
    """Bytes of a 2-D array in VMEM: rows pad to the sublane tile (8 rows
    of 32 bits), columns to the 128-lane tile."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * max(4 // itemsize, 1)
    rows, cols = shape
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def _shapes(arrays) -> str:
    return " + ".join(f"{tuple(a.shape)} {np.dtype(a.dtype).name}"
                      for a in arrays)


def check_staged(kernel: str, vmem=(), smem=(), *, remedy: str) -> None:
    """Raise if the whole-array VMEM blocks ``vmem`` or the
    scalar-prefetched ``smem`` arrays exceed the kernel budgets;
    ``remedy`` tells the caller what to change."""
    need = sum(vmem_bytes(a.shape, a.dtype) for a in vmem)
    if need > VMEM_STAGED_BYTES:
        raise ValueError(
            f"{kernel}: the Pallas kernel stages {_shapes(vmem)} whole in "
            f"VMEM, {need / 2**20:.1f} MiB padded, over its "
            f"{VMEM_STAGED_BYTES / 2**20:.0f} MiB budget; {remedy}")
    need = sum(a.size * np.dtype(a.dtype).itemsize for a in smem)
    if need > SMEM_PREFETCH_BYTES:
        raise ValueError(
            f"{kernel}: the Pallas kernel prefetches {_shapes(smem)} into "
            f"SMEM, {need / 2**10:.0f} KiB, over its "
            f"{SMEM_PREFETCH_BYTES / 2**10:.0f} KiB budget; {remedy}")
