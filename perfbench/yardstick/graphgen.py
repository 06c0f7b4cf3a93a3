"""Seeded DC-SBM graphs at the published widths of a deployment file.

A copy of the system's preset generator (``graphs/synthetic.py``): a
degree-corrected stochastic block model with lognormal degree weights,
a homophily share of same-class edges, and features that are a class
projection plus Gaussian noise.  It differs in two ways, both so that a
benchmark run does the same work on every seed:

* every count is exact.  Class sizes, the train set, and the number of
  undirected edges are fixed numbers, not draws.  Same-class edges are
  drawn without replacement, weighted by the degree weights
  (Efraimidis-Spirakis keys).  Where a class has fewer pairs than the
  homophily share asks for, at most ``intra_pair_cap`` of its pairs are
  taken and the rest of the degree is drawn across classes, so the
  published degree holds and the realised homophily is lower.
* the structure (edges, labels, train set) comes from the deployment's
  fixed ``graph_seed``; the run's ``--seed`` draws the feature values.
  The partition, the shards and every padded shape are then the same on
  every seed, and only the numbers in them change.

Everything is numpy in bulk; nothing here imports the system under test.
"""

from __future__ import annotations

import numpy as np


def _intra_pairs(labels: np.ndarray, num_classes: int):
    """All unordered same-class pairs (u < v), as two int64 arrays."""
    us, vs = [], []
    for c in range(num_classes):
        m = np.nonzero(labels == c)[0]
        iu, iv = np.triu_indices(len(m), 1)
        us.append(m[iu])
        vs.append(m[iv])
    return np.concatenate(us), np.concatenate(vs)


def _inter_pairs(rng, labels, theta, count: int):
    """``count`` distinct cross-class pairs, endpoints drawn by degree
    weight, in draw order."""
    n = len(labels)
    p = theta / theta.sum()
    keys = np.zeros(0, np.int64)
    while len(keys) < count:
        want = int((count - len(keys)) * 1.3) + 1024
        a = rng.choice(n, size=want, p=p)
        b = rng.choice(n, size=want, p=p)
        ok = labels[a] != labels[b]
        lo, hi = np.minimum(a[ok], b[ok]), np.maximum(a[ok], b[ok])
        keys = np.concatenate([keys, lo * n + hi])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:count]
    return keys // n, keys % n


def structure(cfg: dict) -> dict:
    """Edges (CSR over in-edges, rows sorted), labels and train mask of
    the deployment's graph; a function of the configuration alone."""
    rng = np.random.default_rng(int(cfg["graph_seed"]))
    n = int(cfg["vertices"])
    num_classes = int(cfg["classes"])
    labels = rng.permutation(np.arange(n) % num_classes).astype(np.int32)
    theta = rng.lognormal(mean=0.0, sigma=0.9, size=n)
    theta /= theta.mean()

    n_edges = int(round(n * float(cfg["avg_degree"]) / 2))
    iu, iv = _intra_pairs(labels, num_classes)
    n_intra = min(int(round(float(cfg["homophily"]) * n_edges)),
                  int(float(cfg["intra_pair_cap"]) * len(iu)))
    # weighted sampling without replacement: the n_intra largest keys
    # log(U) / w, w = theta_u * theta_v
    key = np.log(rng.random(len(iu))) / (theta[iu] * theta[iv])
    pick = np.argpartition(-key, n_intra - 1)[:n_intra]
    eu, ev = _inter_pairs(rng, labels, theta, n_edges - n_intra)
    src = np.concatenate([iu[pick], eu, iv[pick], ev])
    dst = np.concatenate([iv[pick], ev, iu[pick], eu])
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])

    train_mask = np.zeros(n, bool)
    train_mask[rng.permutation(n)[: int(round(float(cfg["train_frac"]) * n))]] \
        = True
    return {"indptr": indptr, "indices": src.astype(np.int32),
            "labels": labels, "train_mask": train_mask,
            "intra_edges": n_intra, "edges": n_edges}


def features(cfg: dict, labels: np.ndarray, seed: int) -> np.ndarray:
    """(V, F) float32: a seeded class projection plus seeded noise."""
    rng = np.random.default_rng([int(seed), 1])
    num_classes, width = int(cfg["classes"]), int(cfg["features"])
    proj = rng.standard_normal((num_classes, width), dtype=np.float32)
    noise = rng.standard_normal((len(labels), width), dtype=np.float32)
    noise *= np.float32(cfg["feature_noise"])
    noise += proj[labels]
    return noise


def realised(st: dict) -> dict:
    """Degree, edge count and edge homophily of a built structure."""
    n = len(st["labels"])
    dst = np.repeat(np.arange(n), np.diff(st["indptr"]))
    same = st["labels"][st["indices"]] == st["labels"][dst]
    return {"vertices": n, "directed_edges": int(len(st["indices"])),
            "avg_degree": len(st["indices"]) / n,
            "edge_homophily": float(same.mean()),
            "train_vertices": int(st["train_mask"].sum())}
