"""The least work a served batch needs, from the configuration's shapes and
the graph's edges alone, never from how the program lays them out.

Each layer's shapes come from its model kind (``layer_widths``). Counts
are per batch of ``b`` requests over a graph of ``v`` vertices and
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


def layer_widths(kind: str, widths: Sequence[int], **keys) -> list:
    """Each layer as the kind's file (``bench/kinds/<kind>.py``) describes
    it: input width ``fi``, output width ``fo``, the columns its
    neighbour-sum runs over (``sum_width``), whether that sum also reads
    every vertex's own row (``self_loops``), and ``dense_work(v, e, b)``,
    the rest of the layer. ``keys`` are the configuration's model keys
    past ``kind`` and ``widths``."""
    return list(spec.model_kind(kind).layer_widths(widths, **keys))


def _sum_edges(layer, v: int, e: int) -> int:
    return e + v if layer.self_loops else e


def served_spmm(layers, v: int, e: int, b: int,
                halo: int = 0) -> Tuple[float, float]:
    """The neighbour-sums of one batch through ``layers`` (``layer_widths``
    of the model), each over its own width and edges."""
    flops = nbytes = 0.0
    for layer in layers:
        f, n = spmm(_sum_edges(layer, v, e), v, v, layer.sum_width, b, halo)
        flops += f
        nbytes += n
    return flops, nbytes


def served_model(layers, v: int, e: int, b: int,
                 halo: int = 0) -> Tuple[float, float]:
    """The whole model for one batch through ``layers``: each layer's
    neighbour-sum and the rest of it (its ``dense_work``), reading its
    input rows and the edges once and writing its output rows once."""
    flops = nbytes = 0.0
    for layer in layers:
        fi, fo = layer.fi, layer.fo
        edges = _sum_edges(layer, v, e)
        dense_flops, dense_bytes = layer.dense_work(v, e, b)
        flops += 2.0 * edges * layer.sum_width * b + dense_flops
        nbytes += (4.0 * b * v * (fi + fo) + 8.0 * edges
                   + b * halo * (fi + 8.0) + dense_bytes)
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
