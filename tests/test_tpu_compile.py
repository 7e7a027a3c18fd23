"""Compile the GNN kernels for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: slices that are not
tile-aligned, scalar stores into VMEM, index arrays that overflow SMEM and
blocks past the scoped-VMEM limit.  These tests lower each kernel's
``ops.py`` entry point for one chip of a described ``v5e:2x2`` and
compile it at graphsage-products widths (and the reddit width for the
gather), so such a fault fails here instead of on the chip.  The
topology is described inside a fixture, never at import: one process at
a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_gather_agg.ops import gather_aggregate
from repro.kernels.gather.ops import cache_gather
from repro.kernels.segment_agg.ops import neighbor_agg

CHUNK = 4096                   # core/feature_plane.py GATHER_CHUNK_ROWS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # no fallback: without the TPU compiler these tests must error, not skip
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_has_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return "tpu_custom_call" in compiled.as_text()


# cache tables at the default Θ=40 MB (configs/gnn.py): products caches all
# 98,000 rows of F=100; reddit's F=602 leaves room for 17,418 rows
@pytest.mark.parametrize("C,F", [(98_000, 100), (17_418, 602)],
                         ids=["products", "reddit"])
def test_cache_gather_compiles(one_chip, C, F):
    assert _compile_has_kernel(lambda s, c: cache_gather(s, c),
                               _spec(one_chip, (CHUNK,), jnp.int32),
                               _spec(one_chip, (C, F)))


# graphsage-products hops (fanout (15,10,5), batch 512, 98k nodes): the
# level caps of graph/batch.py compute_level_caps, hidden 256 above the
# input hop
@pytest.mark.parametrize("Nd,fanout,Ns,F", [(512, 15, 8_192, 256),
                                            (8_192, 10, 90_112, 256),
                                            (90_112, 5, 98_000, 100)],
                         ids=["hop-seeds", "hop-mid", "hop-input"])
def test_neighbor_agg_compiles(one_chip, Nd, fanout, Ns, F):
    assert _compile_has_kernel(lambda i, h: neighbor_agg(i, h),
                               _spec(one_chip, (Nd, fanout), jnp.int32),
                               _spec(one_chip, (Ns, F)))


def test_neighbor_agg_weighted_compiles(one_chip):
    """The GAT weighted sum at the widest hidden-width hop."""
    assert _compile_has_kernel(
        lambda i, h, w: neighbor_agg(i, h, mode="sum", weights=w),
        _spec(one_chip, (8_192, 10), jnp.int32),
        _spec(one_chip, (90_112, 256)), _spec(one_chip, (8_192, 10)))


@pytest.mark.parametrize("Nd,fanout,Ns", [(90_112, 5, 98_000),
                                          (512, 15, 8_192)],
                         ids=["hop-input", "hop-seeds"])
def test_gather_aggregate_compiles(one_chip, Nd, fanout, Ns):
    """The fused layer-0 read against the full products cache table and
    an 8,192-row miss sideband."""
    assert _compile_has_kernel(
        lambda e, i, c, a: gather_aggregate(e, i, c, a),
        _spec(one_chip, (Ns,), jnp.int32),
        _spec(one_chip, (Nd, fanout), jnp.int32),
        _spec(one_chip, (98_000, 100)), _spec(one_chip, (8_192, 100)))


def test_over_budget_shapes_raise_clear_errors(one_chip):
    """Past the staging budgets the wrappers refuse before compiling: a
    cache table too large for VMEM, an encoding too large for SMEM."""
    with pytest.raises(ValueError, match="VMEM.*cache_volume_mb"):
        jax.jit(lambda s, c: cache_gather(s, c)).lower(
            _spec(one_chip, (CHUNK,), jnp.int32),
            _spec(one_chip, (210_000, 100)))
    with pytest.raises(ValueError, match="SMEM"):
        jax.jit(lambda e, i, c, a: gather_aggregate(e, i, c, a)).lower(
            _spec(one_chip, (300_000,), jnp.int32),
            _spec(one_chip, (512, 15), jnp.int32),
            _spec(one_chip, (1_024, 100)), _spec(one_chip, (8, 100)))
