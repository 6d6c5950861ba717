"""Plain numpy reference of what a served request returns.

It imports nothing of the program. From the benchmark's own raw edge list,
the benchmark's weights and one request's upload it computes:

  * the DAQ collect as the fogs see it: degree quartiles pick 64/32/16/8
    bits per vertex (64 = verbatim), each row linearly quantized to
    2**b - 1 levels between its min and max (float64, then float32);
  * the GNN forward of the paper's Table I:
      GCN   h_v' = act(((sum_{u->v} h_u + h_v) / (deg_v + 1)) W + b)
      SAGE  h_v' = l2norm(act(mean_{u->v} h_u W[:F] + h_v W[F:] + b))
    with ReLU between layers and no activation after the last, and
      GAT   h_v' = ELU(concat_k sum_{u in N(v)+v} a_vu^k h_u W_k)
    with its heads averaged and no activation in the last layer, all in
    float32 with exact sums, rounded where a ``Precision`` says.

The check compares the program with this forward at the precision its
configuration states on the platform (``stated``): on a TPU, XLA's default
precision rounds both operands of a float32 dense matmul to bfloat16 and
accumulates in float32, and the Pallas SpMM may round its operands so
too; an answer is held to the nearer of those two roundings.
``CONTROL`` is the forward one precision lower, as a program that keeps
its features and activations in bfloat16 would compute it on the chip:
every array stored in bfloat16, every matmul on bfloat16 operands with
float32 accumulation. It has to fail the check.

Where the graph is split over fogs and the configuration states that rows
cross between chips as 8-bit codes (``Precision.halo``), ``forward`` takes
``wire``, true for each directed edge whose sender and receiver sit on
different fogs. Over such an edge the receiver reads the sender's row
after a per-row round trip, all in float32: over the layer's input width,
``lo = min``, ``step = max(max - lo, 1e-12) / 255``, codes
``clip(rint((h - lo) / step), 0, 255)``, value ``code * step + lo``. The
layer then reads that value where it would read the exact row (GCN and
SAGE: as the SpMM's feature operand, with its rounding). Local edges and
the vertex's own row read the exact row.

Each model kind's layer lives in ``bench/kinds/<kind>.py``
(``reference_layer``), found by name as ``bench/spec.py`` says.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np
import scipy.sparse

from bench import spec

BF16 = np.dtype(ml_dtypes.bfloat16)


class Edges(NamedTuple):
    """Directed edges sorted by receiver."""
    senders: np.ndarray
    receivers: np.ndarray
    degree: np.ndarray      # in-degree per vertex
    num_vertices: int
    adj: scipy.sparse.csr_matrix   # [receiver, sender] ones, float64


def directed_edges(num_vertices: int, raw: np.ndarray) -> Edges:
    """Self loops dropped, both directions of every pair, duplicates
    removed: the undirected graph the raw (u, v) pairs describe."""
    raw = np.asarray(raw, np.int64).reshape(-1, 2)
    raw = raw[raw[:, 0] != raw[:, 1]]
    both = np.concatenate([raw, raw[:, ::-1]])
    key = np.unique(both[:, 1] * num_vertices + both[:, 0])
    receivers, senders = np.divmod(key, num_vertices)
    degree = np.bincount(receivers, minlength=num_vertices)
    adj = scipy.sparse.csr_matrix(
        (np.ones(len(key)), (receivers, senders)),
        shape=(num_vertices, num_vertices))
    return Edges(senders, receivers, degree, num_vertices, adj)


def daq(feats: np.ndarray, degree: np.ndarray) -> np.ndarray:
    """The default DAQ upload round trip, in float64, returned as float32."""
    x = np.asarray(feats, np.float64)
    q = np.quantile(degree, [0.25, 0.5, 0.75]).astype(np.int64)
    d1 = max(1, int(q[0]))
    d2 = max(d1, int(q[1]))
    d3 = max(d2, int(q[2]))
    bits = np.select([degree >= d3, degree >= d2, degree >= d1],
                     [8, 16, 32], 64)
    out = x.copy()
    lossy = bits < 64
    rows = x[lossy]
    levels = (2.0 ** bits[lossy] - 1.0)[:, None]
    lo = rows.min(axis=1, keepdims=True)
    step = np.maximum(rows.max(axis=1, keepdims=True) - lo, 1e-12) / levels
    out[lossy] = np.clip(np.rint((rows - lo) / step), 0, levels) * step + lo
    return out.astype(np.float32)


class Precision(NamedTuple):
    """Where the forward rounds to a lower type (None: float32 kept).

    ``store``: every array kept between operations (the upload, each
    neighbour-sum, each layer's output); ``dense``: both operands of the
    dense matmuls; ``spmm``: the operands of the neighbour-sum (the rows
    it sums and, where a kind weighs its edges, the weights);
    ``halo``: the integer codes of the rows that cross between fogs.
    Element-wise arithmetic runs in float32 and sums accumulate exactly,
    then round to float32, as the chip's matrix unit accumulates."""
    store: Optional[np.dtype] = None
    dense: Optional[np.dtype] = None
    spmm: Optional[np.dtype] = None
    halo: Optional[np.dtype] = None


CONTROL = Precision(store=BF16, dense=BF16, spmm=BF16)


def stated(config: dict, platform: str) -> Tuple[Precision, ...]:
    """The roundings that the precision the configuration states admits
    on this platform (``precision.rounding`` of the configuration file):
    where the chip's matrix unit may round the operands of a float32
    matmul at the compiler's default precision. An answer is compared
    with the nearest of them."""
    def one(rounding):
        return Precision(**{k: BF16 if v == "bfloat16" else np.dtype(v)
                            for k, v in rounding.items()})
    return tuple(one(r) for r in config["precision"]["rounding"][platform])


def _round(x: np.ndarray, dt: Optional[np.dtype]) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return x if dt is None else x.astype(dt).astype(np.float32)


def _matmul(x: np.ndarray, w: np.ndarray, dt) -> np.ndarray:
    return (_round(x, dt).astype(np.float64)
            @ _round(w, dt).astype(np.float64)).astype(np.float32)


def wire_round_trip(h: np.ndarray, codes: np.dtype) -> np.ndarray:
    """Each row as it reads after crossing the wire as ``codes`` (uint8:
    255 levels) with a float32 (step, min) pair, all in float32."""
    h = np.asarray(h, np.float32)
    levels = np.float32(np.iinfo(codes).max)
    lo = h.min(axis=-1, keepdims=True)
    step = np.maximum(h.max(axis=-1, keepdims=True) - lo,
                      np.float32(1e-12)) / levels
    return np.clip(np.rint((h - lo) / step), 0, levels) * step + lo


class Layer(NamedTuple):
    """What one layer of the reference reads: a kind's ``reference_layer``
    gets it. ``parts`` pairs each adjacency ([receiver, sender] ones) with
    the rows its senders are read as, unrounded: one pair, or the local
    edges with the exact rows (``h`` itself) and the wire's edges with
    the round-tripped ones. ``spmm`` is the rounding of the
    neighbour-sum's operands: ``neighbour_sum`` applies it to those rows,
    and a kind that sums rows it computes itself applies it to both of
    its sum's operands (``spmm_operand``)."""
    h: np.ndarray               # [V, F] input rows as stored
    deg: np.ndarray             # [V, 1] float32 in-degree
    parts: Tuple[Tuple[scipy.sparse.csr_matrix, np.ndarray], ...]
    store: Optional[np.dtype]
    dense: Optional[np.dtype]
    spmm: Optional[np.dtype]

    def neighbour_sum(self) -> np.ndarray:
        """[V, F] sum over u->v of the sender's row, rounded as the SpMM's
        operand, summed exactly, then rounded as stored."""
        (adj, rows), *rest = self.parts
        a = adj @ self.spmm_operand(rows).astype(np.float64)
        for adj, rows in rest:
            a = a + adj @ self.spmm_operand(rows).astype(np.float64)
        return self.stored(a.astype(np.float32))

    def matmul(self, x: np.ndarray, w: np.ndarray) -> np.ndarray:
        return _matmul(x, w, self.dense)

    def stored(self, x: np.ndarray) -> np.ndarray:
        """``x`` as kept between operations."""
        return _round(x, self.store)

    def spmm_operand(self, x: np.ndarray) -> np.ndarray:
        """``x`` as an operand of a neighbour-sum."""
        return _round(x, self.spmm)


def _adjacency(e: Edges, keep: np.ndarray) -> scipy.sparse.csr_matrix:
    return scipy.sparse.csr_matrix(
        (np.ones(int(keep.sum())), (e.receivers[keep], e.senders[keep])),
        shape=(e.num_vertices, e.num_vertices))


def forward(kind: str, params: Sequence[dict], e: Edges, feats: np.ndarray,
            prec: Precision = Precision(),
            wire: Optional[np.ndarray] = None
            ) -> Tuple[np.ndarray, np.ndarray]:
    """[V, F] collected features -> ([V, D] float32 embeddings, [V] row
    scales), rounded where ``prec`` says; ``wire`` marks the directed
    edges (in ``e``'s order) that cross between fogs, whose senders' rows
    go through ``wire_round_trip`` where ``prec.halo`` is set.

    A row's scale is its norm before SAGE's final L2 normalisation (1 for
    GCN). A row whose norm is near 0 there points anywhere after it, so
    rounding alone turns it round; ``scaled_rms`` weighs each row's error
    by this scale, which compares the rows as they were before the
    normalisation amplified their rounding."""
    model = spec.model_kind(kind)
    scale = np.ones(e.num_vertices, np.float32)
    h = _round(feats, prec.store)
    deg = e.degree.astype(np.float32)[:, None]
    split = wire is not None and prec.halo is not None
    if split:
        wire = np.asarray(wire, bool)
        adj = (_adjacency(e, ~wire), _adjacency(e, wire))
    for i, p in enumerate(params):
        p = {k: np.asarray(v, np.float32) for k, v in p.items()}
        parts = (((adj[0], h), (adj[1], wire_round_trip(h, prec.halo)))
                 if split else ((e.adj, h),))
        out, s = model.reference_layer(
            p, Layer(h, deg, parts, prec.store, prec.dense, prec.spmm),
            i == len(params) - 1)
        if s is not None:
            scale = s
        h = _round(out, prec.store)
    return h, scale


def scaled_rms(got: np.ndarray, ref: np.ndarray, scale: np.ndarray) -> float:
    """The root mean square of the error over that of the reference, each
    entry weighed by its row's scale. A missing, misshapen or non-finite
    answer reads infinite."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    s = np.asarray(scale, np.float64)[:, None]
    num = np.sqrt(np.sum(np.square((got - ref) * s)))
    return float(num / max(np.sqrt(np.sum(np.square(ref * s))), 1e-30))
