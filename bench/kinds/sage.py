"""GraphSAGE with the mean aggregator (Hamilton et al.), as Fograph's
Table I serves it:

    h_v' = l2norm(act(mean_{u->v} h_u W[:F] + h_v W[F:] + b))

Its weight stacks the neighbour mean's rows over the vertex's own.
"""
import numpy as np

from bench import dense


def layer_widths(widths):
    """Each layer as the work counts read it (``dense.Shape``)."""
    return [dense.Shape(fi, 2 * fi, fo, fi)
            for fi, fo in zip(widths[:-1], widths[1:])]


def init(key, widths):
    return dense.glorot_layers(key, layer_widths(widths))


def reference_layer(p, x, last):
    """One layer of the reference over ``x`` (``reference.Layer``):
    (output rows, each row's norm before the L2 normalisation)."""
    f = x.h.shape[-1]
    out = (x.matmul(x.neighbour_sum() / np.maximum(x.deg, 1.0), p["w"][:f])
           + x.matmul(x.h, p["w"][f:]) + p["b"])
    if not last:
        out = np.maximum(out, 0.0)
    norm = np.sqrt(np.sum(np.square(out.astype(np.float64)), axis=-1,
                          keepdims=True))
    return ((out / np.maximum(norm, 1e-12)).astype(np.float32),
            norm[:, 0].astype(np.float32))
