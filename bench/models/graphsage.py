"""GraphSAGE with the mean aggregator: the model's part of the plain
reference (``bench/reference.py`` holds what every model shares).

A model is ``L`` layers of ``h_dst @ W_self + mean(h_neighbours) @ W_neigh
+ b`` (ReLU between layers) over sampled blocks; the parameters are laid
out as the program's ``models/gnn.py:decls_gnn`` lays out GraphSAGE's.

``train_counts`` counts one training step on a batch's REAL level sizes:
padding is work the algorithm does not need.  Per layer with ``n``
destination rows, fanout ``f`` and widths ``din -> dout``:

- forward: the neighbour mean, ``n*f*din`` adds, and two matmuls,
  ``2 * 2*n*din*dout``;
- backward: the two weight gradients, ``2 * 2*n*din*dout``, and, for
  every layer but the input one (features need no gradient), the input
  gradients, ``2 * 2*n*din*dout``, and the scatter of the mean,
  ``n*f*din``.

Biases, activations, the loss and the optimizer are O(n*dout) or
O(params) and left out.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dot


def sage_dims(feat_dim: int, hidden: int, num_classes: int,
              num_layers: int):
    dims = [feat_dim] + [hidden] * (num_layers - 1) + [num_classes]
    return list(zip(dims[:-1], dims[1:]))


def dims(config: dict):
    """``(din, dout)`` of each layer, input layer first."""
    return sage_dims(config["feat_dim"], config["hidden"],
                     config["num_classes"], config["num_layers"])


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


def init_weights(seed: int, config: dict) -> dict:
    """The weights of ``--seed``: one jitted call on the default device,
    float32, normal with std ``1/sqrt(din)``, zero biases, in the layout
    ``{"layers": [{"w_self", "w_neigh", "b"}, ...]}``."""
    return _make_weights(jax.random.PRNGKey(seed),
                         tuple(tuple(d) for d in dims(config)))


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
        out = (dot(h[:idx.shape[0]], p["w_self"].astype(dtype), mode)
               + dot(mean, p["w_neigh"].astype(dtype), mode)
               + p["b"].astype(dtype))
        h = jax.nn.relu(out) if i < len(layers) - 1 else out
    return h.astype(jnp.float32)


def sage_train_flops(sizes: Sequence[int], fanouts: Sequence[int],
                     dims) -> int:
    """``sizes``: real node count per level, input level first (one more
    entry than layers); ``fanouts``: per layer, input layer first."""
    total = 0
    for i, ((din, dout), f) in enumerate(zip(dims, fanouts)):
        n = int(sizes[i + 1])
        mm = 4 * n * din * dout
        total += n * f * din + mm          # forward
        total += mm                        # weight gradients
        if i:
            total += mm + n * f * din      # input gradients
    return total


def train_counts(sizes: Sequence[int], fanouts: Sequence[int],
                 config: dict) -> dict:
    return {"flops": sage_train_flops(sizes, fanouts, dims(config))}
