"""Benchmark runner: one function per paper table/figure.

Prints ``name,value,note`` CSV rows plus per-benchmark wall time. Kernel
micro-benchmarks report us_per_call and name the JAX backend they ran on.
Set FULL=1 for paper-scale graphs. A benchmark that raises is reported as
an ``/ERROR`` row, the rest still run, and the runner exits 1.

The roofline/dry-run analysis lives in ``benchmarks.roofline`` (reads
results/dryrun produced by ``repro.launch.dryrun``) because it needs a
512-device process.
"""
from __future__ import annotations

import time

import numpy as np


def kernel_microbench():
    """us/call for the Pallas kernels. Off-TPU they run in interpret mode,
    so the numbers only track relative block shapes; the note names the
    backend."""
    import jax

    from repro.gnn import datasets
    from repro.kernels import ops
    from repro.kernels.ops import dequantize_features

    g = datasets.load("yelp", scale=0.1, seed=0)
    rng = np.random.default_rng(0)
    h = rng.normal(size=(g.num_vertices, 128)).astype(np.float32)
    bc = ops.BlockCsr(g)
    bc.aggregate(h)  # compile
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        bc.aggregate(h)
    agg_us = (time.perf_counter() - t0) / reps * 1e6
    codes = rng.integers(0, 255, (g.num_vertices, 128)).astype(np.uint8)
    sc = rng.uniform(0.01, 1, g.num_vertices).astype(np.float32)
    mn = rng.normal(size=g.num_vertices).astype(np.float32)
    dequantize_features(codes, sc, mn)
    t0 = time.perf_counter()
    for _ in range(reps):
        dequantize_features(codes, sc, mn)
    dq_us = (time.perf_counter() - t0) / reps * 1e6
    backend = jax.default_backend()
    mode = "compiled" if backend == "tpu" else "interpret mode"
    note = f"{backend} ({mode})"
    return [("kernel/block_spmm_us_per_call", agg_us, note),
            ("kernel/dequant_us_per_call", dq_us, note)]


def main() -> int:
    from benchmarks import paper_figures

    total_t0 = time.time()
    failed = []
    print("name,value,note")
    for fn in paper_figures.ALL + [kernel_microbench]:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — run the rest, fail at the end
            print(f"{fn.__name__}/ERROR,nan,{type(e).__name__}: {e}")
            failed.append(fn.__name__)
            continue
        for name, value, note in rows:
            if isinstance(value, float):
                print(f"{name},{value:.6g},{note}")
            else:
                print(f"{name},{value},{note}")
        print(f"# {fn.__name__} took {time.time() - t0:.1f}s")
    print(f"# total {time.time() - total_t0:.1f}s")
    if failed:
        print(f"# FAILED: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
