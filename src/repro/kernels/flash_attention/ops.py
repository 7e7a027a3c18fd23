"""jit wrapper for flash attention: (B,S,H,Dh) layout + fallback dispatch."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.platform import pallas_on_platform


@functools.partial(jax.jit,
                   static_argnames=("causal", "use_pallas", "block_q",
                                    "block_k"))
def flash_attention(q, k, v, causal: bool = True, use_pallas: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """q/k/v (B, S, H, Dh) — same-head-count (repeat GQA beforehand)."""
    B, S, H, Dh = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    unfold = lambda x: x.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
    if use_pallas:
        o = pallas_on_platform(
            functools.partial(flash_attention_pallas, causal=causal,
                              block_q=block_q, block_k=block_k),
            fold(q), fold(k), fold(v))
    else:
        o = attention_ref(fold(q), fold(k), fold(v), causal=causal)
    return unfold(o)
