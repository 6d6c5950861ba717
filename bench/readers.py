"""What the per-layer metrics read, from one traced run.

``m`` is the run as the harness hands it to ``bench/metrics/<name>.py``:
``m.spans`` (host spans, ``bench/spans.py``), ``m.trace`` (the device
trace's reduction, ``bench/trace.py``), ``m.run`` (the loop's batches),
``m.b`` (the built cell: model kind, widths and other model keys,
vertices, edges, halo rows, and ``sizes``, the batch size of each
Response as the Server counted it),
``m.peak`` (the chip's peaks) and ``m.chips``. Each function returns None
where the run has nothing to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import trace, work


def span_ms(m, name: str) -> Optional[float]:
    """Mean milliseconds of one ``name`` span."""
    n = m.spans.count(name) if m.spans else 0
    if not n:
        return None
    return 1e3 * m.spans.total(name) / n


def server_ms(m) -> Optional[float]:
    """Milliseconds per drain that are neither collect nor execute."""
    n = m.spans.count("drain") if m.spans else 0
    if not n:
        return None
    inner = m.spans.total("collect") + m.spans.total("execute")
    return 1e3 * (m.spans.total("drain") - inner) / n


def batch_size(m) -> Optional[float]:
    return float(np.mean(m.b.sizes)) if m.b.sizes else None


def device_idle(m) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the
    chip, averaged over the cell's chips."""
    if m.trace is None or not m.trace.busy_s or m.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - float(np.mean(m.trace.busy_s)) / m.trace.window_s)


def _least(m, count) -> float:
    """Least seconds of the traced batches' work, one layer at a time, each
    layer as its model kind describes it."""
    b = m.b
    layers = work.layer_widths(b.kind, b.widths, **b.model_keys)
    total = 0.0
    for batch in m.run.batches:
        n = len(batch.ids)
        for layer in layers:
            total += work.least_seconds(
                *count([layer], b.v, b.e, n, b.halo), m.peak)
    return total


def spmm_roofline(m) -> Optional[float]:
    """Per cent: the least time of the neighbour-sums over the device time
    of the SpMM kernels (summed over chips)."""
    if m.trace is None or m.peak is None:
        return None
    kernel = trace.seconds_matching(m.trace, trace.SPMM_KERNEL)
    if kernel <= 0:
        return None
    return 100.0 * _least(m, work.served_spmm) / kernel


def mfu(m) -> Optional[float]:
    """Per cent: the least time of the whole model's work over the traced
    batches, over chips times the traced window."""
    if m.trace is None or m.peak is None or m.trace.window_s <= 0:
        return None
    return 100.0 * _least(m, work.served_model) / (
        m.chips * m.trace.window_s)


def halo_ms(m) -> Optional[float]:
    """Milliseconds per batch of collective operations on the device,
    averaged over the chips."""
    if m.trace is None or not m.run.batches or m.trace.chips < 2:
        return None
    coll = trace.seconds_matching(m.trace, trace.COLLECTIVE)
    if coll <= 0:
        return None
    return 1e3 * coll / m.trace.chips / len(m.run.batches)
