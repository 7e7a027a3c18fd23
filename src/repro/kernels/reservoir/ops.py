"""jit wrapper: pads rows/lanes to hardware tiles, dispatches kernel/ref."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.platform import pallas_on_platform
from repro.kernels.reservoir.kernel import reservoir_topm_pallas
from repro.kernels.reservoir.ref import reservoir_topm_ref


def _pad_to(x, rows, cols, value):
    R, C = x.shape
    return jnp.pad(x, ((0, rows - R), (0, cols - C)), constant_values=value)


@functools.partial(jax.jit, static_argnames=("m", "use_pallas"))
def reservoir_topm(weights, u, mask, m: int, use_pallas: bool = True):
    """Top-m ES selection over padded neighbor rows.

    weights (R,N) f32; u (R,N) uniforms; mask (R,N) bool/int.
    Returns (idx (R,m) int32 — N_padded marks exhausted, keys (R,m))."""
    R, N = weights.shape
    Rp = -(-R // 8) * 8
    Np = max(-(-N // 128) * 128, 128)
    wp = _pad_to(weights.astype(jnp.float32), Rp, Np, 1.0)
    up = _pad_to(u.astype(jnp.float32), Rp, Np, 0.0)
    mp = _pad_to(mask.astype(jnp.int32), Rp, Np, 0)
    if use_pallas:
        idx, keys = pallas_on_platform(
            functools.partial(reservoir_topm_pallas, m=m), wp, up, mp)
    else:
        idx, keys = reservoir_topm_ref(wp, up, mp, m)
    idx = jnp.where(idx >= Np, N, idx)     # normalize exhausted marker
    return idx[:R], keys[:R]
