"""The benchmark's traffic: Experiment II point sets, subsamples, pools.

The generators are copies of the program's ``repro.data.synthetic`` ones
(mlbench-style 2-D shapes of the GPIC paper's Experiment II), kept here so
that no change to the program can move the inputs it is measured on. So is
the adjusted Rand index. Everything is numpy and deterministic in its seed.
"""
from __future__ import annotations

import numpy as np


def _split_counts(n: int, k: int) -> list[int]:
    base = n // k
    counts = [base] * k
    for i in range(n - base * k):
        counts[i] += 1
    return counts


def cassini(n: int, rng: np.random.Generator):
    counts = _split_counts(n, 3)
    xs = []
    for cls, sign in ((0, 1.0), (1, -1.0)):
        t = rng.uniform(0.2 * np.pi, 0.8 * np.pi, counts[cls])
        r = rng.uniform(1.6, 2.4, counts[cls])
        xs.append(np.stack([r * np.cos(t), sign * r * np.sin(t)], axis=1))
    t = rng.uniform(0, 2 * np.pi, counts[2])
    r = 0.45 * np.sqrt(rng.uniform(0, 1, counts[2]))
    xs.append(np.stack([r * np.cos(t), r * np.sin(t)], axis=1))
    return xs


def gaussians(n: int, rng: np.random.Generator, k: int = 4,
              spread: float = 0.35):
    xs = []
    for cls, cnt in enumerate(_split_counts(n, k)):
        ang = 2.0 * np.pi * cls / k
        center = 3.0 * np.array([np.cos(ang), np.sin(ang)])
        xs.append(center + rng.normal(0.0, spread, (cnt, 2)))
    return xs


def shapes(n: int, rng: np.random.Generator):
    counts = _split_counts(n, 4)
    xs = [np.array([-3.0, 3.0]) + rng.normal(0, 0.3, (counts[0], 2)),
          np.array([3.0, 3.0]) + rng.uniform(-0.7, 0.7, (counts[1], 2))]
    u = rng.uniform(0, 1, counts[2])
    v = rng.uniform(0, 1, counts[2])
    su = np.sqrt(u)
    a, b, c = (np.array([-0.9, -0.8]), np.array([0.9, -0.8]),
               np.array([0.0, 0.8]))
    tri = ((1 - su)[:, None] * a + (su * (1 - v))[:, None] * b
           + (su * v)[:, None] * c)
    xs.append(np.array([-3.0, -3.0]) + tri)
    t = rng.uniform(0, 2 * np.pi, counts[3])
    r = rng.normal(0.8, 0.05, counts[3])
    xs.append(np.array([3.0, -3.0])
              + np.stack([r * np.cos(t), r * np.sin(t)], axis=1))
    return xs


def smiley(n: int, rng: np.random.Generator):
    counts = _split_counts(n, 4)
    xs = [np.array([-0.8, 1.0]) + rng.normal(0, 0.15, (counts[0], 2)),
          np.array([0.8, 1.0]) + rng.normal(0, 0.15, (counts[1], 2))]
    yy = rng.uniform(-0.4, 0.4, counts[2])
    half_w = 0.12 * (0.4 - yy) / 0.8 + 0.02
    xx = rng.uniform(-1.0, 1.0, counts[2]) * half_w
    xs.append(np.stack([xx, yy], axis=1))
    t = rng.uniform(np.pi * 1.15, np.pi * 1.85, counts[3])
    r = rng.normal(1.3, 0.04, counts[3])
    xs.append(np.stack([r * np.cos(t), 0.3 + r * np.sin(t)], axis=1))
    return xs


DATASETS = {"cassini": cassini, "gaussians": gaussians, "shapes": shapes,
            "smiley": smiley}


def draw(name: str, n: int, rng: np.random.Generator):
    """One draw of ``name`` at n points, rows shuffled (users' data come in
    no class order): (x float32 (n, 2), y int32 (n,), k)."""
    parts = DATASETS[name](n, rng)
    x = np.concatenate(parts).astype(np.float32)
    y = np.concatenate([np.full(len(p), c, np.int32)
                        for c, p in enumerate(parts)])
    perm = rng.permutation(n)
    return x[perm], y[perm], len(parts)


def subsample_balanced(x, y, fraction: float, rng: np.random.Generator):
    """Experiment II's balanced subsample: equal draws from every class."""
    classes = np.unique(y)
    per_class = max(max(int(round(len(y) * fraction)), len(classes))
                    // len(classes), 1)
    idx = np.concatenate([
        rng.choice(np.flatnonzero(y == c),
                   size=min(per_class, int(np.sum(y == c))), replace=False)
        for c in classes])
    rng.shuffle(idx)
    return x[idx], y[idx]


def make_pool(traffic: dict, n: int, seed: int):
    """The cell's pool of inputs: a list of (x, y) and k.

    The point sets are fixed by the traffic file, so every seed brings the
    same work: ``traffic["draws"]`` seeds one draw of ``traffic["dataset"]``
    at the configuration's n each, or, with ``subsample`` (a fraction),
    one draw at ``traffic["draws"][0]`` and a balanced subsample for each
    of ``traffic["subsamples"]``, as in the paper's Experiment II. The
    run's ``seed`` shuffles the rows of every input (users' data come in
    no class order) and the order in which the client sends them.
    """
    name, frac = traffic["dataset"], traffic.get("subsample")
    if frac is None:
        sets = [draw(name, n, np.random.default_rng(d))
                for d in traffic["draws"]]
        k = sets[0][2]
        sets = [(x, y) for x, y, _ in sets]
    else:
        x, y, k = draw(name, n, np.random.default_rng(traffic["draws"][0]))
        sets = [subsample_balanced(x, y, float(frac),
                                   np.random.default_rng(s))
                for s in traffic["subsamples"]]
    rng = np.random.default_rng(seed)
    pool = []
    for i in rng.permutation(len(sets)):
        x, y = sets[i]
        perm = rng.permutation(len(y))
        pool.append((x[perm], y[perm]))
    return pool, k


def _contingency(a, b) -> np.ndarray:
    _, ai = np.unique(np.asarray(a).ravel(), return_inverse=True)
    _, bi = np.unique(np.asarray(b).ravel(), return_inverse=True)
    c = np.zeros((ai.max() + 1, bi.max() + 1), np.int64)
    np.add.at(c, (ai, bi), 1)
    return c


def adjusted_rand_index(labels_true, labels_pred) -> float:
    """ARI (Hubert & Arabie 1985): 1 for identical partitions, ~0 for
    chance agreement."""
    c = _contingency(labels_true, labels_pred)

    def comb2(v):
        v = np.asarray(v, np.float64)
        return v * (v - 1.0) / 2.0

    sum_ij = comb2(c).sum()
    a, b = comb2(c.sum(axis=1)).sum(), comb2(c.sum(axis=0)).sum()
    expected = a * b / max(float(comb2(c.sum())), 1.0)
    max_index = 0.5 * (a + b)
    if max_index == expected:
        return 1.0 if sum_ij == max_index else 0.0
    return float((sum_ij - expected) / (max_index - expected))


#: squared standardized distance within which a point is as near another
#: centroid as its own: f32 rounding of the distances and the last
#: Lloyd step's centroid shift move a nearest-centroid decision by far
#: less (a standardized embedding has unit spread)
TIE = 1e-3


def labels_out_of_place(v, labels, k: int) -> int:
    """Points that a nearest-centroid assignment of the 1-D embedding ``v``
    would not give their label, plus clusters missing of ``k``.

    The centroids are the means of the standardized embedding over each
    label. A point counts when its own centroid is farther than another
    by more than ``TIE``; k-means output reads 0, a point handed another
    cluster's label reads 1.
    """
    z = np.asarray(v, np.float64)
    z = (z - z.mean()) / max(z.std(), 1e-300)
    lab = np.asarray(labels)
    ids = np.unique(lab)
    cents = np.array([z[lab == i].mean() for i in ids])
    d2 = (z[:, None] - cents[None, :]) ** 2
    own = d2[np.arange(len(z)), np.searchsorted(ids, lab)]
    return int(np.sum(own - d2.min(axis=1) > TIE)) + (k - len(ids))
