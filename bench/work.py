"""The least work a served batch needs, from the configuration's shapes and
the graph's edges alone, never from how the program lays them out.

Counts are per batch of ``b`` requests over a graph of ``v`` vertices and
``e`` directed edges. ``halo`` is the number of (vertex, other fog) pairs
in which a fog reads a vertex that another fog owns (0 on one chip); those
rows cross between chips as uint8 codes with 8 bytes of (scale, min) per
row.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from bench import spec


def spmm(e: int, v_src: int, v_out: int, f: int, b: int,
         halo: int = 0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one neighbour-sum over ``f`` features: every edge
    multiplies and adds one source row; every source row is read once as
    float32 (halo rows as codes), every output row written once, and
    every edge's two int32 indices read once."""
    flops = 2.0 * e * f * b
    nbytes = 4.0 * b * (v_src + v_out) * f + 8.0 * e + b * halo * (f + 8.0)
    return flops, nbytes


def layer_widths(kind: str, widths: Sequence[int]):
    """(input width, weight rows, output width) of each layer, as the
    kind's file (``bench/kinds/<kind>.py``) lays out its weights."""
    return spec.model_kind(kind).layer_widths(widths)


def served_spmm(kind: str, widths: Sequence[int], v: int, e: int, b: int,
                halo: int = 0) -> Tuple[float, float]:
    """The neighbour-sums of one batch through every layer."""
    flops = nbytes = 0.0
    for fi, _, _ in layer_widths(kind, widths):
        f, n = spmm(e, v, v, fi, b, halo)
        flops += f
        nbytes += n
    return flops, nbytes


def served_model(kind: str, widths: Sequence[int], v: int, e: int, b: int,
                 halo: int = 0) -> Tuple[float, float]:
    """The whole model for one batch: each layer's neighbour-sum and dense
    update (the kind's ``dense_work``), reading its input rows and the
    edges once and writing its output rows once."""
    model = spec.model_kind(kind)
    flops = nbytes = 0.0
    for fi, rows, fo in layer_widths(kind, widths):
        dense_flops, dense_bytes = model.dense_work(v, fi, rows, fo, b)
        flops += 2.0 * e * fi * b + dense_flops
        nbytes += (4.0 * b * v * (fi + fo) + 8.0 * e + b * halo * (fi + 8.0)
                   + dense_bytes)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip of ``peak`` needs: the larger of the FLOPs at
    its peak rate and the bytes at its memory bandwidth."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def halo_pairs(senders: np.ndarray, receivers: np.ndarray,
               owner: np.ndarray) -> int:
    """Distinct (sender, reading fog) pairs over edges that cross fogs."""
    s = np.asarray(senders, np.int64)
    fog = np.asarray(owner, np.int64)[np.asarray(receivers)]
    cross = fog != np.asarray(owner)[s]
    nfog = int(np.max(owner)) + 1
    return int(np.unique(s[cross] * nfog + fog[cross]).size)
