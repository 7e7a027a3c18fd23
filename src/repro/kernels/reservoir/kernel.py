"""Pallas kernel: vectorized weighted-reservoir (Efraimidis–Spirakis) top-m.

TPU-native reformulation of the paper's sequential Algo. 2: instead of a
per-neighbor heap loop (CPU-idiomatic, O(deg) serial), compute all keys
``log(u)/w`` for a padded neighbor row at once on the VPU and take the top-m
by m rounds of (max, mask) — identical sampling distribution, fully
data-parallel over rows and lanes.

Layout: rows = dst vertices (8/block, sublane-aligned), lanes = padded
neighbor slots (multiple of 128).  m is small (fanout ≤ 32) so the m-round
selection stays in VMEM registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.0e38


def _topm_kernel(w_ref, u_ref, mask_ref, idx_ref, key_ref, *, m: int):
    w = w_ref[...]                                   # (Rb, Npad) f32
    u = u_ref[...]
    valid = mask_ref[...] != 0
    # ES keys in log space: log(u)/w  (monotone in u^{1/w})
    keys = jnp.log(jnp.maximum(u, 1e-30)) / jnp.maximum(w, 1e-9)
    keys = jnp.where(valid, keys, NEG)
    npad = keys.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    for j in range(m):                               # static fanout rounds
        mx = jnp.max(keys, axis=1, keepdims=True)    # (Rb,1)
        is_max = (keys == mx) & (mx > NEG / 2)
        # first index attaining the max (lane-order tie-break)
        idx = jnp.min(jnp.where(is_max, iota, npad), axis=1)  # (Rb,)
        idx_ref[:, j] = idx.astype(jnp.int32)
        key_ref[:, j] = mx[:, 0]
        # mask the chosen lane
        chosen = iota == idx[:, None]
        keys = jnp.where(chosen, NEG, keys)


def reservoir_topm_pallas(weights: jnp.ndarray, u: jnp.ndarray,
                          mask: jnp.ndarray, m: int, *, interpret: bool,
                          block_rows: int = 8):
    """weights/u (R, Npad) f32, mask (R, Npad) int32 → (idx (R,m) i32,
    keys (R,m) f32).  idx = Npad marks an exhausted row (fewer than m valid)."""
    R, npad = weights.shape
    assert R % block_rows == 0, (R, block_rows)
    grid = (R // block_rows,)
    bs_in = pl.BlockSpec((block_rows, npad), lambda r: (r, 0))
    bs_out = pl.BlockSpec((block_rows, m), lambda r: (r, 0))
    return pl.pallas_call(
        functools.partial(_topm_kernel, m=m),
        grid=grid,
        in_specs=[bs_in, bs_in, bs_in],
        out_specs=[bs_out, bs_out],
        out_shape=[jax.ShapeDtypeStruct((R, m), jnp.int32),
                   jax.ShapeDtypeStruct((R, m), jnp.float32)],
        interpret=interpret,
    )(weights, u, mask.astype(jnp.int32))
