"""Plain float32 GraphSAGE: weights from the seed, forward, loss, AdamW.

Independent of the program: nothing here imports ``repro``.  A model is
``L`` layers of ``h_dst @ W_self + mean(h_neighbours) @ W_neigh + b``
(ReLU between layers) over sampled blocks: block ``i`` is an
``(n_dst, fanout)`` matrix of row indices into the previous level, ``-1``
for an empty slot, and the destination rows are the first ``n_dst`` rows
of that level.  The loss is the mean softmax cross-entropy over the
seeds; the optimizer is AdamW as the config file states it.

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


@functools.partial(jax.jit, static_argnums=(1,))
def _make_weights(key, dims):
    keys = jax.random.split(key, 2 * len(dims))
    out = []
    for i, (din, dout) in enumerate(dims):
        scale = 1.0 / np.sqrt(din)
        out.append({"w_self": jax.random.normal(keys[2 * i], (din, dout))
                    * scale,
                    "w_neigh": jax.random.normal(keys[2 * i + 1], (din, dout))
                    * scale,
                    "b": jnp.zeros((dout,), jnp.float32)})
    return {"layers": out}


def init_weights(seed: int, dims) -> dict:
    """The weights of ``--seed``: one jitted call on the default device,
    float32, normal with std ``1/sqrt(din)``, zero biases, in the layout
    ``{"layers": [{"w_self", "w_neigh", "b"}, ...]}``."""
    return _make_weights(jax.random.PRNGKey(seed),
                         tuple(tuple(d) for d in dims))


def _dot(a, b, mode: str):
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


def forward(params, feats, idxs, mode: str = "f32"):
    """``mode``: ``f32`` (the reference), ``bf16`` (activations and weights
    in bfloat16) or ``bf16x3`` (float32 with three-pass matmuls)."""
    dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    h = feats.astype(dtype)
    layers = params["layers"]
    for i, (p, idx) in enumerate(zip(layers, idxs)):
        mask = idx >= 0
        nb = jnp.where(mask[..., None], h[jnp.maximum(idx, 0)], 0)
        cnt = jnp.maximum(mask.sum(1, keepdims=True), 1).astype(dtype)
        mean = nb.sum(1) / cnt
        out = (_dot(h[:idx.shape[0]], p["w_self"].astype(dtype), mode)
               + _dot(mean, p["w_neigh"].astype(dtype), mode)
               + p["b"].astype(dtype))
        h = jax.nn.relu(out) if i < len(layers) - 1 else out
    return h.astype(jnp.float32)


def loss_fn(params, feats, idxs, labels, mode: str = "f32"):
    logits = forward(params, feats, idxs, mode)[:labels.shape[0]]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("mode", "opt"))
def _train_step(params, m, v, t, feats, idxs, labels, mode, opt):
    lr, b1, b2, eps, wd = opt
    loss, g = jax.value_and_grad(loss_fn)(params, feats, idxs, labels, mode)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v, loss, g


@functools.partial(jax.jit, static_argnames=("mode",))
def _forward_jit(params, feats, idxs, mode):
    return forward(params, feats, idxs, mode)


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


def train(params, batches, opt: dict, mode: str = "f32"):
    """AdamW from ``params`` over ``batches`` of ``(feats, neigh, labels)``;
    returns per-step losses, the first step's gradient and the final
    parameters, all on the host."""
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
                np.asarray(labels, np.int32), mode=mode, opt=optv)
            losses.append(float(loss))
            if g0 is None:
                g0 = jax.tree.map(np.asarray, g)
    return losses, g0, jax.tree.map(np.asarray, params)


def logits(params, feats, neigh, pads, mode: str = "f32") -> np.ndarray:
    """Forward pass with every level padded to ``pads``; all rows of the
    last level."""
    f, idxs = pad_block(feats, neigh, pads)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_forward_jit(params, f, idxs, mode=mode))
