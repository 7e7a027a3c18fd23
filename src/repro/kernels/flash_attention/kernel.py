"""Pallas kernel: blockwise fused (flash) attention forward, causal/full.

Grid (batch·heads, q_blocks); the kernel streams KV blocks through VMEM with
an online-softmax running (max, sum, acc) state.  Block shapes are
MXU-aligned: q/kv blocks multiples of 128 lanes on Dh, sublane-tiled on the
sequence dims.  Causal masking prunes fully-masked KV blocks via the loop
bound (no wasted MXU work above the diagonal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                  seq_len: int, causal: bool, sm_scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (Bq, Dh)
    m_i = jnp.full((block_q,), NEG, jnp.float32)
    l_i = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    n_kv = seq_len // block_k
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)

    def body(kv_i, carry):
        m_i, l_i, acc = carry
        # direct ref indexing (pl.load rejects plain-int axes on some
        # jax versions; ref.__getitem__ normalizes them)
        k = k_ref[0, pl.dslice(kv_i * block_k, block_k),
                  slice(None)].astype(jnp.float32)
        v = v_ref[0, pl.dslice(kv_i * block_k, block_k),
                  slice(None)].astype(jnp.float32)
        s = q @ k.T                                      # (Bq, Bk)
        if causal:
            kv_pos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= kv_pos, s, NEG)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc

    if causal:
        upper = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
    else:
        upper = n_kv
    m_i, l_i, acc = jax.lax.fori_loop(0, upper, body, (m_i, l_i, acc))
    o_ref[0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, interpret: bool, causal: bool = True,
                           block_q: int = 128, block_k: int = 128):
    """q/k/v (BH, S, Dh) → (BH, S, Dh).  S must divide by the blocks."""
    BH, S, Dh = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    sm_scale = Dh ** -0.5
    grid = (BH, S // block_q)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, causal=causal, sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, Dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, Dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
