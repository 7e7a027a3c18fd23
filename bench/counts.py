"""Bytes the work needs, from its shapes.

A model's operations are counted by its own module,
``bench/models/<model>.py:train_counts`` (GraphSAGE's FLOPs:
``bench/models/graphsage.py``).  ``gather_bytes`` is what a cache gather
must move: each hit row read from the table once, every row written once
(a miss comes back as a zero row), plus each row's 4-byte slot.
"""
from __future__ import annotations


def gather_bytes(rows: int, hits: int, feat_dim: int,
                 itemsize: int = 4) -> int:
    row = int(feat_dim) * itemsize
    return int(hits) * row + int(rows) * (row + 4)
