"""The benchmark's graphs, made from a seed.

The arithmetic is that of the paper's Table III stand-ins in the program
(R-MAT topology at the dataset's size with Graph500's Kronecker parameters,
community labels by label propagation, and either SIoT-style sparse one-hot
attribute blocks or smoothed dense features), copied here so that the
yardstick does not change when the program does. The benchmark hands the
raw edge list to the program's graph constructor, and the reference builds
its own directed edge set from the same list.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

# Table III: vertices, undirected edges, feature width, label classes.
TABLE_III = {
    "siot": dict(vertices=16216, edges=146117, feature=52, labels=2),
    "yelp": dict(vertices=10000, edges=15683, feature=100, labels=2),
    "rmat-20k": dict(vertices=20_000, edges=199_000, feature=32, labels=8),
}


class RawGraph(NamedTuple):
    num_vertices: int
    edges: np.ndarray       # int64[E0, 2] (u, v) pairs as generated
    features: np.ndarray    # float32[V, F] stored features


def rmat_edges(num_vertices: int, num_edges: int, rng: np.random.Generator,
               a: float = 0.57, b: float = 0.19, c: float = 0.19
               ) -> np.ndarray:
    """R-MAT recursive generator [Chakrabarti et al., SDM'04]."""
    scale = int(np.ceil(np.log2(max(2, num_vertices))))
    probs = np.array([a, b, c, 1.0 - a - b - c])
    rows = np.zeros(num_edges, dtype=np.int64)
    cols = np.zeros(num_edges, dtype=np.int64)
    for level in range(scale):
        q = rng.choice(4, size=num_edges, p=probs)
        half = 1 << (scale - level - 1)
        rows += np.where((q == 2) | (q == 3), half, 0)
        cols += np.where((q == 1) | (q == 3), half, 0)
    keep = (rows < num_vertices) & (cols < num_vertices) & (rows != cols)
    return np.stack([rows[keep], cols[keep]], axis=1)


def community_labels(n: int, edges: np.ndarray, classes: int,
                     rng: np.random.Generator, iters: int = 8) -> np.ndarray:
    """Label propagation: each vertex takes its neighbourhood's majority
    (its own label counts once); ties go to the lowest class."""
    labels = rng.integers(0, classes, size=n)
    if edges.shape[0] == 0 or classes <= 1:
        return labels.astype(np.int32)
    s, r = edges[:, 0], edges[:, 1]
    for _ in range(iters):
        idx = np.concatenate([r * classes + labels[s],
                              s * classes + labels[r],
                              np.arange(n) * classes + labels])
        votes = np.bincount(idx, minlength=n * classes).reshape(n, classes)
        labels = votes.argmax(axis=1)
    return labels.astype(np.int32)


def onehot_blocks(dim: int):
    """(first column, width) of each categorical block of SIoT-style
    features: device type, brand, mobility and so on."""
    blocks = max(2, dim // 13)
    per = dim // blocks
    return [(blk * per, per if blk < blocks - 1 else dim - blk * per)
            for blk in range(blocks)]


def features(n: int, edges: np.ndarray, dim: int, rng: np.random.Generator,
             sparse_onehot: bool, labels: np.ndarray) -> np.ndarray:
    if sparse_onehot:
        out = np.zeros((n, dim), dtype=np.float32)
        for blk, (base, width) in enumerate(onehot_blocks(dim)):
            if blk == 0:
                # The first block correlates with the label.
                cat = (labels * width // max(1, labels.max() + 1)) % width
                noise = rng.integers(0, width, size=n)
                flip = rng.random(n) < 0.15
                cat = np.where(flip, noise, cat)
            else:
                cat = rng.integers(0, width, size=n)
            out[np.arange(n), base + cat] = 1.0
        return out
    # Dense embedding-like features (Yelp word2vec / R-MAT node2vec).
    x = rng.normal(size=(n, dim)).astype(np.float32)
    centers = rng.normal(size=(int(labels.max()) + 1, dim)).astype(np.float32)
    x = 0.7 * centers[labels] + 0.5 * x
    if edges.shape[0]:
        s, r = edges[:, 0], edges[:, 1]
        deg = np.bincount(r, minlength=n) + 1.0
        for _ in range(2):
            agg = np.zeros_like(x)
            np.add.at(agg, r, x[s])
            x = (x + agg / deg[:, None]).astype(np.float32) * 0.5
    return x


def distinct_edges(n: int, e: int, rng: np.random.Generator,
                   rounds: int = 64) -> np.ndarray:
    """The first ``e`` distinct undirected pairs of an R-MAT stream, in the
    order drawn, each as its first (u, v); R-MAT draws repeat pairs, so
    it draws until ``e`` distinct ones are in hand."""
    pairs = np.zeros((0, 2), np.int64)
    for _ in range(rounds):
        more = rmat_edges(n, int((e - len(pairs)) * 1.35) + 64, rng)
        pairs = np.concatenate([pairs, more])
        key = np.minimum(pairs[:, 0], pairs[:, 1]) * n + np.maximum(
            pairs[:, 0], pairs[:, 1])
        _, first = np.unique(key, return_index=True)
        pairs = pairs[np.sort(first)]
        if len(pairs) >= e:
            return pairs[:e]
    raise ValueError(f"R-MAT gave {len(pairs)} distinct pairs of {e} over "
                     f"{n} vertices in {rounds} rounds")


def make(dataset: str, scale: float, seed: int) -> RawGraph:
    """The ``dataset`` stand-in at ``scale`` of its Table III size: as
    many vertices and distinct undirected edges as the table gives."""
    stats = TABLE_III[dataset]
    rng = np.random.default_rng(seed)
    n = max(8, int(stats["vertices"] * scale))
    e = max(n, int(stats["edges"] * scale))
    edges = distinct_edges(n, e, rng)
    labels = community_labels(n, edges, max(1, stats["labels"]), rng)
    feats = features(n, edges, stats["feature"], rng, dataset == "siot",
                     labels)
    return RawGraph(n, edges, feats)
