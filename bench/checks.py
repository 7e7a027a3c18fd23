"""The numbers that decide ``correct``, and their limits.

Training (three checked steps from the same weights):

- ``loss_gap``: largest ``|loss - ref| / |ref|`` over the steps;
- ``grad_gap``: the first gradient as the optimizer got it, worst leaf:
  ``| |g| - |g_ref| |`` over ``max(|g_ref leaf|, |g_ref median leaf|)``;
- ``update_gap``: the parameters' change after the checked steps, by the
  same worst-leaf measure.  A leaf whose reference gradient is under a
  thousandth of the median leaf's moves by round-off alone under Adam and
  is left out of it;
- ``rows_bad``: feature elements the plane returned that differ from the
  graph's rows (exact, limit 0);
- ``sample_bad``: sampled neighbours that are not neighbours in the CSR
  (exact, limit 0).

Serving (a seeded sample of finished requests):

- ``logit_gap``: largest ``|served - ref|`` logit over the sample, over
  the median request's largest reference logit magnitude.  A served
  answer is the request's whole logit row, so the row is compared; the
  gap by which the served class's reference logit lies below the best
  reads 0 for the control on most seeds and separates nothing.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _leaves(tree) -> List[np.ndarray]:
    out = []

    def walk(t):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(t[k])
        elif isinstance(t, (list, tuple)):
            for x in t:
                walk(x)
        else:
            out.append(np.asarray(t, np.float64))
    walk(tree)
    return out


def worst_leaf_gap(got, ref, keep=None) -> float:
    g = [np.linalg.norm(x) for x in _leaves(got)]
    r = [np.linalg.norm(x) for x in _leaves(ref)]
    idx = [i for i in range(len(r)) if keep is None or keep[i]]
    med = float(np.median([r[i] for i in idx]))
    return max(abs(g[i] - r[i]) / max(r[i], med) for i in idx)


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` per checked step, ``g0``
    (the first gradient), ``params0`` and ``params_end``."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], ref["losses"]))
    norms = np.array([np.linalg.norm(x) for x in _leaves(ref["g0"])])
    keep = norms >= 1e-3 * np.median(norms)

    def change(side):
        return [a - b for a, b in zip(_leaves(side["params_end"]),
                                      _leaves(side["params0"]))]
    return {"loss_gap": float(loss_gap),
            "grad_gap": float(worst_leaf_gap(prog["g0"], ref["g0"])),
            "update_gap": float(worst_leaf_gap(change(prog), change(ref),
                                               keep))}


def rows_bad(got: np.ndarray, want: np.ndarray) -> int:
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def sample_bad(indptr: np.ndarray, indices: np.ndarray, blocks) -> int:
    """Sampled ``(dst, neighbour)`` pairs absent from the CSR."""
    bad = 0
    n = len(indptr) - 1
    for dst_ids, src_ids, neigh in blocks:
        rows, cols = np.nonzero(neigh >= 0)
        if not len(rows):
            continue
        u = np.asarray(dst_ids, np.int64)[rows]
        v = np.asarray(src_ids, np.int64)[neigh[rows, cols]]
        uu = np.unique(u)
        d = indptr[uu + 1] - indptr[uu]
        start = np.repeat(indptr[uu], d)
        off = np.arange(int(d.sum())) - np.repeat(np.cumsum(d) - d, d)
        have = np.repeat(uu, d) * n + indices[start + off].astype(np.int64)
        bad += int((~np.isin(u * n + v, have)).sum())
    return bad


def serve_readings(served: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """``served``/``ref``: (requests, classes) logits."""
    scale = float(np.median(np.abs(ref).max(axis=1)))
    return {"logit_gap": float(np.abs(served - ref).max() / scale)}


def judge(readings: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """Every reading beside its limit; a reading with no limit is an
    error in the limits file, not a pass."""
    missing = sorted(set(readings) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return {k: {"value": readings[k], "limit": limits[k]}
            for k in sorted(readings)}


def passed(judged: Dict[str, dict]) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in judged.values())

