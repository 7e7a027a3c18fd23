"""Power-law twin of a published graph dataset, built once per checkout.

A copy of ``powerlaw_graph`` (``src/repro/graph/synthetic.py``) and the
``from_edges`` it calls, with one change: each node's expected out-degree
is capped at ``sqrt(num_edges)``.  That is the Chung–Lu condition that no
edge probability exceeds 1; without it the top node of a published-size
twin expects millions of out-edges (1.9M on ogbn-products, 15.7M on
Reddit) and nearly every sampled frontier touches it.  The excess mass of
the capped nodes is spread over the others in proportion to their weight,
so the number of entries and the exponent of the tail are unchanged.

With ``degree_cap=None`` the arrays equal ``powerlaw_graph``'s bit for bit
(``bench/tests/test_graphgen.py``).  Two steps are computed differently
for speed and give identical results: the weighted draws are numpy's
``Generator.choice`` inverse-CDF search split over threads, and the CSR
order is one sort of ``src << 32 | position`` instead of a stable argsort.

The graph is the dataset, so it comes from the config's ``dataset_seed``
and never from the run's ``--seed``.  ``load_or_build`` keeps it under
``bench/.cache/`` keyed by config name, dataset parameters, seed and
``GEN_VERSION``; any change to those builds it again.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

GEN_VERSION = 1
CACHE_DIR = Path(__file__).resolve().parent / ".cache"
ARRAYS = ("indptr", "indices", "features", "labels", "train_mask",
          "val_mask", "test_mask")
# dataset keys of a config file that define the graph
DATASET_KEYS = ("num_nodes", "num_edges", "feat_dim", "num_classes",
                "power_exp", "homophily", "degree_cap", "dataset_seed",
                "train_frac", "val_frac")
_CHUNK = 1 << 22


def capped_probs(p: np.ndarray, m: int, cap: float) -> np.ndarray:
    """``p`` with every entry's expected count ``m * p_i`` held at ``cap``;
    the mass taken off is given to the uncapped entries in proportion."""
    p = p.astype(np.float64).copy()
    pmax = cap / m
    capped = np.zeros(len(p), bool)
    while True:
        over = (p > pmax) & ~capped
        if not over.any():
            return p / p.sum()
        capped |= over
        excess = float(p[over].sum() - pmax * over.sum())
        p[over] = pmax
        free = ~capped
        p[free] += excess * p[free] / p[free].sum()


def _weighted_draw(rng: np.random.Generator, p: np.ndarray, m: int,
                   threads: int) -> np.ndarray:
    """``rng.choice(len(p), size=m, p=p)``, same stream and result: numpy
    draws ``rng.random(m)`` and searches the normalized CDF; the search
    is split over threads."""
    cdf = p.cumsum()
    cdf /= cdf[-1]
    u = rng.random(m)
    out = np.empty(m, np.int32)

    def search(a):
        out[a:a + _CHUNK] = np.searchsorted(cdf, u[a:a + _CHUNK],
                                            side="right")

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(search, range(0, m, _CHUNK)))
    return out


def build(num_nodes: int, num_edges: int, power_exp: float, feat_dim: int,
          num_classes: int, homophily: float = 0.7, seed: int = 0,
          degree_cap=None, train_frac: float = 0.66,
          val_frac: float = 0.1, threads: int = 8,
          feat_chunk_rows: int = 65536) -> dict:
    """The twin's arrays: CSR ``indptr``/``indices``, ``features``,
    ``labels`` and the three split masks.  ``degree_cap`` is ``None``
    (``powerlaw_graph`` exactly) or ``"sqrt_edges"``."""
    rng = np.random.default_rng(seed)
    n, m = int(num_nodes), int(num_edges)

    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    labels = labels[np.argsort(labels, kind="stable")]
    class_start = np.searchsorted(labels, np.arange(num_classes))
    class_end = np.searchsorted(labels, np.arange(num_classes), side="right")

    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-1.0 / (power_exp - 1.0))
    rng.shuffle(w)
    p = w / w.sum()
    if degree_cap == "sqrt_edges":
        p = capped_probs(p, m, np.sqrt(m))
    elif degree_cap is not None:
        raise ValueError(f"unknown degree_cap {degree_cap!r}")

    src = _weighted_draw(rng, p, m, threads)
    dst = _weighted_draw(rng, p, m, threads)
    flip = rng.random(m) < homophily
    r = rng.random(m)
    for a in range(0, m, _CHUNK):
        sl = slice(a, a + _CHUNK)
        cls = labels[src[sl]]
        lo, hi = class_start[cls], class_end[cls]
        same = lo + (r[sl] * np.maximum(hi - lo, 1)).astype(np.int64)
        d = np.where(flip[sl], same.astype(np.int32), dst[sl])
        dst[sl] = np.where(src[sl] == d, (d + 1) % n, d)
    del flip, r

    centers = rng.normal(0, 1.0, size=(num_classes, feat_dim)).astype(
        np.float32)
    features = np.empty((n, feat_dim), np.float32)
    for a in range(0, n, feat_chunk_rows):
        b = min(a + feat_chunk_rows, n)
        features[a:b] = centers[labels[a:b]] + rng.normal(
            0, 2.0, size=(b - a, feat_dim)).astype(np.float32)

    # from_edges: CSR by a stable order of src
    key = (src.astype(np.int64) << 32) | np.arange(m, dtype=np.int64)
    del src
    key.sort()
    indices = dst[(key & 0xFFFFFFFF)]
    counts = np.bincount((key >> 32).astype(np.int64), minlength=n)
    del key, dst
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    split = np.random.default_rng(seed).random(n)
    train = split < train_frac
    val = (split >= train_frac) & (split < train_frac + val_frac)
    return {"indptr": indptr, "indices": indices.astype(np.int32),
            "features": features, "labels": labels, "train_mask": train,
            "val_mask": val, "test_mask": ~train & ~val}


def dataset_params(cfg: dict) -> dict:
    return {k: cfg[k] for k in DATASET_KEYS if k in cfg}


def cache_key(cfg: dict) -> str:
    blob = json.dumps({"name": cfg["name"], "gen": GEN_VERSION,
                       **dataset_params(cfg)}, sort_keys=True)
    return f"{cfg['name']}-{hashlib.sha256(blob.encode()).hexdigest()[:16]}"


def load_or_build(cfg: dict, cache_dir: Path = CACHE_DIR,
                  log=print) -> dict:
    """The config's twin from ``cache_dir``, built and saved on first use.
    Entries of other keys for the same config name are removed, so a
    changed config leaves one graph on disk."""
    cache_dir = Path(cache_dir)
    key = cache_key(cfg)
    path = cache_dir / key
    if not (path / "done").exists():
        for old in cache_dir.glob(f"{cfg['name']}-*"):
            shutil.rmtree(old, ignore_errors=True)
        d = dataset_params(cfg)
        seed = d.pop("dataset_seed")
        arrays = build(seed=seed, threads=min(os.cpu_count() or 1, 8), **d)
        tmp = cache_dir / f"{key}.partial"
        shutil.rmtree(tmp, ignore_errors=True)
        tmp.mkdir(parents=True)
        for k, a in arrays.items():
            np.save(tmp / f"{k}.npy", a)
        (tmp / "done").write_text(key)
        tmp.rename(path)
        log(f"[graph] built {key}")
        return arrays
    log(f"[graph] loaded {key}")
    return {k: np.load(path / f"{k}.npy") for k in ARRAYS}
