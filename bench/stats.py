"""Percentiles, sample counts and the closed loop's whole-batch window."""
from __future__ import annotations

import math
import statistics
from typing import Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile, interpolated between order statistics."""
    return float(np.percentile(np.asarray(values, float), q))


def beyond(n: int, q: float) -> int:
    """How many of n samples lie above the q-th percentile's rank."""
    return n - math.ceil(q / 100.0 * n)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, over the median."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def whole_batch_rate(batches, seconds: float) -> Tuple[float, int, float]:
    """(requests/s, requests counted, window length) over whole batches.

    The window opens at the first batch's completion and closes at the
    last completion inside ``seconds``; the requests counted are those of
    the batches that complete after the opening, up to the close. So a
    batch is either wholly in the window or wholly out of it."""
    inside = [b for b in batches if b.end <= seconds]
    if len(inside) < 2:
        raise ValueError(f"{len(inside)} batch(es) completed inside "
                         f"{seconds} s; a rate needs two")
    count = sum(len(b.ids) for b in inside[1:])
    span = inside[-1].end - inside[0].end
    return count / span, count, span
