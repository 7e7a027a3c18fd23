"""Gradient compression for the slow cross-pod (DCN) data-parallel axis.

Two schemes, both with error feedback (residual carried to the next step so
compression error doesn't bias convergence):

  * int8 uniform quantization with per-tensor (or per-row) scales —
    4× volume reduction vs f32, 2× vs bf16
  * top-k sparsification — k·(4+4) bytes per tensor

``compressed_psum_int8`` is the shard_map building block: quantize locally,
all-reduce the int8 payload (as int32 accumulators to avoid overflow),
dequantize — this is what the multi-pod train step uses over the ``pod``
axis, cutting DCN bytes ~4× at the cost of one extra max-reduce for scales.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Quantization primitives
# ---------------------------------------------------------------------------

def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def topk_sparsify(x: jnp.ndarray, frac: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Keep the top-``frac`` entries by magnitude; returns (values, flat idx)."""
    flat = x.reshape(-1)
    k = max(int(frac * flat.size), 1)
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx


def topk_densify(vals: jnp.ndarray, idx: jnp.ndarray, shape) -> jnp.ndarray:
    out = jnp.zeros(int(jnp.prod(jnp.array(shape))), vals.dtype)
    return out.at[idx].set(vals).reshape(shape)


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------

def ef_init(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def ef_compress_int8(grads, residual):
    """(compressed-then-decompressed grads, new residual) with error feedback."""
    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        q, s = quantize_int8(corrected)
        deq = dequantize_int8(q, s)
        return deq.astype(g.dtype), corrected - deq
    pairs = jax.tree.map(one, grads, residual)
    return (jax.tree.map(lambda t: t[0], pairs,
                         is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.map(lambda t: t[1], pairs,
                         is_leaf=lambda x: isinstance(x, tuple)))


def ef_compress_topk(grads, residual, frac: float = 0.05):
    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        vals, idx = topk_sparsify(corrected, frac)
        dense = topk_densify(vals, idx, corrected.shape)
        return dense.astype(g.dtype), corrected - dense
    pairs = jax.tree.map(one, grads, residual)
    return (jax.tree.map(lambda t: t[0], pairs,
                         is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.map(lambda t: t[1], pairs,
                         is_leaf=lambda x: isinstance(x, tuple)))


# ---------------------------------------------------------------------------
# shard_map collective: int8 all-reduce over a named axis
# ---------------------------------------------------------------------------

def compressed_psum_int8(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mean-reduce ``x`` over ``axis_name`` with int8 payload.

    Wire format per tensor: int8 payload (psum'd as int32) + f32 scale
    (max-reduced).  ~4× fewer DCN bytes than f32 ring all-reduce.
    Call inside shard_map with ``axis_name`` bound (e.g. "pod").
    """
    n = jax.lax.psum(1, axis_name)
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0 + 1e-12
    scale = jax.lax.pmax(scale, axis_name)            # shared scale
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    acc = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return (acc.astype(jnp.float32) * scale / n).astype(x.dtype)


def make_crosspod_grad_transform(mesh, kind: str = "int8"):
    """grad_transform hook for make_train_step: reduce grads over the pod
    axis with compression (shard_map over 'pod'; other axes untouched)."""
    from jax.sharding import PartitionSpec as P

    if "pod" not in mesh.axis_names:
        return None

    def transform(grads):
        def red(g):
            fn = jax.shard_map(
                lambda t: compressed_psum_int8(t, "pod"),
                mesh=mesh,
                in_specs=P(*((None,) * g.ndim)),
                out_specs=P(*((None,) * g.ndim)),
                check_vma=False)
            return fn(g)
        return jax.tree.map(red, grads)

    return transform
