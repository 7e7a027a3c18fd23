"""bench/counts.py against hand counts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import counts  # noqa: E402


def test_sage_flops_hand_count():
    # two layers 4 -> 3 -> 2; levels 10 -> 5 -> 2 rows; fanouts 3, 2
    dims = counts.sage_dims(4, 3, 2, 2)
    assert dims == [(4, 3), (3, 2)]
    # layer 0 (input): 5 dst rows, fanout 3, 4 -> 3
    fwd0 = 5 * 3 * 4 + 2 * (2 * 5 * 4 * 3)       # mean + two matmuls
    bwd0 = 2 * (2 * 5 * 4 * 3)                     # weight gradients only
    # layer 1: 2 dst rows, fanout 2, 3 -> 2
    fwd1 = 2 * 2 * 3 + 2 * (2 * 2 * 3 * 2)
    bwd1 = 2 * (2 * 2 * 3 * 2) + 2 * (2 * 2 * 3 * 2) + 2 * 2 * 3
    assert counts.sage_train_flops([10, 5, 2], [3, 2], dims) == \
        fwd0 + bwd0 + fwd1 + bwd1 == 708


def test_gather_bytes():
    # 10 rows, 3 of them hits: 3 rows read, 10 written, 10 slots
    assert counts.gather_bytes(10, 3, 100) == 3 * 400 + 10 * (400 + 4)
