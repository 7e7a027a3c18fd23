"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Production topology: TPU v5e pods of 16×16=256
chips; multi-pod adds a leading ``pod`` axis (cross-pod traffic goes over
DCN — pure data parallelism with optional gradient compression).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules
    (distributed/sharding.py) place arrays with sharding constraints,
    which only ``Auto`` axes accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (axis names preserved)."""
    return _auto_mesh((1, 1), ("data", "model"))


@dataclass(frozen=True)
class HostSimMesh:
    """Host-simulated device mesh for the multi-partition GNN path.

    When the process has fewer devices than partitions (the 1-CPU CI
    container), collectives cannot run as real shard_map programs; this
    stand-in carries the same (axis name, size) topology so the rest of the
    stack — distributed/collectives.grad_allreduce, core/multipart.py — is
    written against one mesh API and swaps in real devices transparently.
    """
    size: int
    axis: str = "part"

    @property
    def axis_names(self):
        return (self.axis,)

    @property
    def shape(self):
        return {self.axis: self.size}


def make_partition_mesh(num_partitions: int, axis: str = "part"):
    """1-D mesh over the data-parallel GNN partitions.

    Real ``Mesh`` over the first ``num_partitions`` devices when the host
    has enough of them; ``HostSimMesh`` otherwise (CI: 1 CPU device, any
    partition count)."""
    devices = jax.devices()
    if num_partitions <= len(devices):
        return Mesh(np.asarray(devices[:num_partitions]), (axis,))
    return HostSimMesh(num_partitions, axis)


# Per-chip peaks, keyed by ``jax.Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect per chip; ici_bw is one of its
# four links).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
        "hbm_bytes": 16 * 1024**3,   # 16 GiB
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
