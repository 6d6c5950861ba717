"""What the model kinds whose layer is one dense update share: their
weights and the work of that update."""
from __future__ import annotations

import math
from typing import Iterable, List, Tuple


def glorot_layers(key, shapes: Iterable[Tuple[int, int, int]]) -> List[dict]:
    """Per layer of (input width, weight rows, output width): Glorot-uniform
    ``w`` [rows, out] and a small uniform bias ``b`` [out], float32, each
    layer from two keys split off the one before (traced under jit)."""
    import jax
    import jax.numpy as jnp

    out = []
    for _, rows, fo in shapes:
        key, kw, kb = jax.random.split(key, 3)
        lim = math.sqrt(6.0 / (rows + fo))
        out.append({"w": jax.random.uniform(kw, (rows, fo), jnp.float32,
                                            -lim, lim),
                    "b": jax.random.uniform(kb, (fo,), jnp.float32,
                                            -0.1, 0.1)})
    return out


def update_work(v: int, rows: int, fo: int, b: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the dense update of ``v`` rows for a batch of
    ``b``: one multiply-add per weight per row, the weights and the bias
    read once."""
    return 2.0 * v * rows * fo * b, 4.0 * (rows * fo + fo)
