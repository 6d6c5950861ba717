"""GCN (Kipf & Welling), as Fograph's Table I serves it:

    h_v' = act(((sum_{u->v} h_u + h_v) / (deg_v + 1)) W + b)
"""
import numpy as np

from bench import dense


def layer_widths(widths):
    """Each layer as the work counts read it (``dense.Shape``)."""
    return [dense.Shape(fi, fi, fo, fi)
            for fi, fo in zip(widths[:-1], widths[1:])]


def init(key, widths):
    return dense.glorot_layers(key, layer_widths(widths))


def reference_layer(p, x, last):
    """One layer of the reference over ``x`` (``reference.Layer``):
    (output rows, None: no row scale)."""
    out = x.matmul((x.neighbour_sum() + x.h) / (x.deg + 1.0), p["w"]) + p["b"]
    return (out if last else np.maximum(out, 0.0)), None
