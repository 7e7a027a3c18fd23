"""What every model's plain float32 reference shares: padding, matmuls
at each precision, the loss and AdamW.

Independent of the program: nothing here or under ``bench/models/``
imports ``repro``.  A model family's own part, its weights from the
seed, forward and counts, is ``bench/models/<model>.py`` (GraphSAGE's is
``bench/models/graphsage.py``); the functions here take that module as
``model``.  Blocks are sampled: block ``i`` is an ``(n_dst, fanout)``
matrix of row indices into the previous level, ``-1`` for an empty slot,
and the destination rows are the first ``n_dst`` rows of that level.
The loss is the mean softmax cross-entropy over the seeds; the optimizer
is AdamW as the config file states it.

Everything runs under ``jax.default_matmul_precision("highest")``.  The
lower-precision control (``control_mode``) keeps float32 master weights
and optimizer state and computes the forward and backward pass one step
below the configuration's precision: for float32 at ``highest``, with
three-pass bfloat16 matmuls (``bf16x3``, XLA's ``high``, written out so
the CPU computes it too); for other float32, in bfloat16 (``bf16``).

Each level is padded to a power of two (serving: to fixed caps), so one
compiled program serves every batch of a cell; padded rows are zero,
padded index rows are empty, and no real row reads a padded one.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def dot(a, b, mode: str):
    """``a @ b`` in ``mode``'s precision: ``bf16x3`` is three bfloat16
    passes, any other mode the operands' own."""
    if mode != "bf16x3":
        return a @ b
    # float32 at three bfloat16 passes (XLA's ``high``), written out so it
    # computes the same on every backend: hi*hi + hi*lo + lo*hi, each part
    # rounded to bfloat16's 8-bit mantissa in float32 (``reduce_precision``,
    # which XLA keeps; a float32 -> bfloat16 -> float32 round trip it may
    # drop as excess precision), so every product is exact in float32
    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    a_hi, b_hi = bf16(a), bf16(b)
    a_lo, b_lo = bf16(a - a_hi), bf16(b - b_hi)
    return a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi


def loss_fn(params, feats, idxs, labels, model, mode: str = "f32"):
    logits = model.forward(params, feats, idxs, mode)[:labels.shape[0]]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("model", "mode", "opt"))
def _train_step(params, m, v, t, feats, idxs, labels, model, mode, opt):
    lr, b1, b2, eps, wd = opt
    loss, g = jax.value_and_grad(loss_fn)(params, feats, idxs, labels,
                                          model, mode)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v, loss, g


@functools.partial(jax.jit, static_argnames=("model", "mode"))
def _forward_jit(params, feats, idxs, model, mode):
    return model.forward(params, feats, idxs, mode)


def pad_block(feats: np.ndarray, neigh: Sequence[np.ndarray],
              pads: Sequence[int]):
    """Level ``i`` padded to ``pads[i]`` rows (input level first)."""
    f = np.zeros((pads[0], feats.shape[1]), np.float32)
    f[:len(feats)] = feats
    idxs = []
    for i, nb in enumerate(neigh):
        m = -np.ones((pads[i + 1], nb.shape[1]), np.int32)
        m[:len(nb)] = nb
        idxs.append(m)
    return f, idxs


def train_pads(feats: np.ndarray, neigh: Sequence[np.ndarray]) -> List[int]:
    """Power-of-two pads per level; the seed level stays exact."""
    sizes = [len(feats)] + [len(nb) for nb in neigh]
    return [pow2(s) for s in sizes[:-1]] + [sizes[-1]]


def control_mode(config: dict) -> str:
    """The lower-precision control of a configuration: three bfloat16
    passes for float32 at ``highest``, bfloat16 for other float32."""
    return "bf16x3" if config["matmul_precision"] == "highest" else "bf16"


def train(model, params, batches, opt: dict, mode: str = "f32"):
    """``model``'s AdamW steps from ``params`` over ``batches`` of
    ``(feats, neigh, labels)``; returns per-step losses, the first step's
    gradient and the final parameters, all on the host."""
    optv = (float(opt["lr"]), float(opt["b1"]), float(opt["b2"]),
            float(opt["eps"]), float(opt["weight_decay"]))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g0 = [], None
    with jax.default_matmul_precision("highest"):
        for t, (feats, neigh, labels) in enumerate(batches, start=1):
            f, idxs = pad_block(feats, neigh, train_pads(feats, neigh))
            params, m, v, loss, g = _train_step(
                params, m, v, jnp.float32(t), f, idxs,
                np.asarray(labels, np.int32), model=model, mode=mode,
                opt=optv)
            losses.append(float(loss))
            if g0 is None:
                g0 = jax.tree.map(np.asarray, g)
    return losses, g0, jax.tree.map(np.asarray, params)


def logits(model, params, feats, neigh, pads, mode: str = "f32"
           ) -> np.ndarray:
    """``model``'s forward pass with every level padded to ``pads``; all
    rows of the last level."""
    f, idxs = pad_block(feats, neigh, pads)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_forward_jit(params, f, idxs, model=model,
                                       mode=mode))
