"""Operations and bytes the work needs, from its shapes.

``sage_train_flops`` counts one GraphSAGE (mean aggregator) training step
on a batch's REAL level sizes: padding is work the algorithm does not
need.  Per layer with ``n`` destination rows, fanout ``f`` and widths
``din -> dout``:

- forward: the neighbour mean, ``n*f*din`` adds, and two matmuls,
  ``2 * 2*n*din*dout``;
- backward: the two weight gradients, ``2 * 2*n*din*dout``, and, for
  every layer but the input one (features need no gradient), the input
  gradients, ``2 * 2*n*din*dout``, and the scatter of the mean,
  ``n*f*din``.

Biases, activations, the loss and the optimizer are O(n*dout) or
O(params) and left out.  ``gather_bytes`` is what a cache gather must
move: each hit row read from the table once, every row written once
(a miss comes back as a zero row), plus each row's 4-byte slot.
"""
from __future__ import annotations

from typing import Sequence


def sage_dims(feat_dim: int, hidden: int, num_classes: int,
              num_layers: int):
    dims = [feat_dim] + [hidden] * (num_layers - 1) + [num_classes]
    return list(zip(dims[:-1], dims[1:]))


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


def gather_bytes(rows: int, hits: int, feat_dim: int,
                 itemsize: int = 4) -> int:
    row = int(feat_dim) * itemsize
    return int(hits) * row + int(rows) * (row + 4)
