"""What the model kinds whose layer is one dense update share: their
weights, their layers as the work counts read them, and the work of that
update."""
from __future__ import annotations

import math
from typing import Iterable, List, NamedTuple, Tuple


class Shape(NamedTuple):
    """One layer as ``bench/work.py`` reads it: a neighbour-sum over the
    input rows, then one dense update."""
    fi: int                   # input width
    rows: int                 # rows of the update's weight
    fo: int                   # output width
    sum_width: int            # columns the neighbour-sum runs over
    self_loops: bool = False  # the sum also reads every vertex's own row

    def dense_work(self, v: int, e: int, b: int) -> Tuple[float, float]:
        return update_work(v, self.rows, self.fo, b)


def glorot_layers(key, shapes: Iterable[Shape]) -> List[dict]:
    """Per layer: Glorot-uniform ``w`` [rows, fo] and a small uniform bias
    ``b`` [fo], float32, each layer from two keys split off the one before
    (traced under jit)."""
    import jax
    import jax.numpy as jnp

    out = []
    for s in shapes:
        key, kw, kb = jax.random.split(key, 3)
        lim = math.sqrt(6.0 / (s.rows + s.fo))
        out.append({"w": jax.random.uniform(kw, (s.rows, s.fo), jnp.float32,
                                            -lim, lim),
                    "b": jax.random.uniform(kb, (s.fo,), jnp.float32,
                                            -0.1, 0.1)})
    return out


def update_work(v: int, rows: int, fo: int, b: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the dense update of ``v`` rows for a batch of
    ``b``: one multiply-add per weight per row, the weights and the bias
    read once."""
    return 2.0 * v * rows * fo * b, 4.0 * (rows * fo + fo)
