#!/usr/bin/env python3
"""Bring-up smoke test: the fog GNN serving path on a TPU chip.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # 4-fog mesh-bsp path, 4 chips

One chip: builds the SIoT graph at full scale (16,216 vertices, F = 52)
and a seeded 2-layer GCN (hidden width 64), compiles it with
``Engine(..., executor="single", aggregation="auto")`` and the default DAQ
compressor, and replays a Poisson trace through ``plan.server(max_batch=8)``
that yields both singleton and multi-request micro-batches, so
``block_spmm`` and ``block_spmm_batched`` both run compiled. Every response
is checked against a plain float32 numpy GCN written here, fed by a numpy
DAQ quantization of the request's upload, and the same trace is served
again with ``aggregation="segment_sum"`` and checked the same way. Last, the
served B=1 and largest-batch programs are timed and their compiled text is
checked for Mosaic kernels.

``--four-chips``: compiles a 4-fog plan (``CLUSTER``, ``mesh-bsp``,
``halo`` exchange, DAQ-quantized halo wire through ``dequant_spmm``),
serves single and batched requests, compares them with the single-program
executor on the same inputs, and checks that each shard ran on its own
device. It runs nothing else.

The script needs a TPU: elsewhere it exits 2 before printing any result.
Every phase runs in this one process, which holds the chip. A failed check
exits 1. The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import numpy as np  # noqa: E402

HIDDEN = 64          # Engine's default ``hidden`` width
MAX_BATCH = 8
CLUSTER = "1A+2B+1C"  # the 4-fog cluster of --four-chips
# Requests and rate of the Poisson trace. At this rate the server's
# simulated clock forms both singleton batches and batches of several
# requests on full-scale SIoT (checked below, not assumed).
N_REQUESTS = 8
RATE = 20.0
# Tolerance of every check against the float32 numpy reference, as the
# max |got - ref| over max |ref| of one response. XLA's default precision
# for an f32 matmul on TPU is one bf16 pass (8-bit mantissa, a relative
# rounding of 2**-9 per operand). The 2-layer GCN chains four matmuls
# (SpMM, dense, SpMM, dense), so a few times 2**-9 of the output's scale
# is expected. A response checked against another request's reference
# must fail it: the script checks that every such cross pair is further
# apart than 2e-2, so this tolerance cannot hide a swapped batch slot.
REF_TOL = 2e-2
# Mesh vs single program: the mesh quantizes the halo wire to 8 bits per
# row (uint8 codes + f32 scale/min) and the single program does not. This
# is the DAQ-fused bound of ``benchmarks/roofline.py --smoke`` (max abs).
DAQ_TOL = 5e-2


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def in_degrees(graph) -> np.ndarray:
    return np.bincount(np.asarray(graph.receivers),
                       minlength=graph.num_vertices)


def reference_daq(feats: np.ndarray, deg: np.ndarray) -> np.ndarray:
    """The default DAQ upload as the fogs see it, in float64 numpy: degree
    quartiles pick 64/32/16/8 bits per vertex (64 = verbatim), and each
    row is linearly quantized to 2**b - 1 levels between its min and max."""
    x = np.asarray(feats, np.float64)
    q = np.quantile(deg, [0.25, 0.5, 0.75]).astype(np.int64)
    d1 = max(1, int(q[0]))
    d2 = max(d1, int(q[1]))
    d3 = max(d2, int(q[2]))
    bits = np.select([deg >= d3, deg >= d2, deg >= d1], [8, 16, 32], 64)
    out = x.copy()
    lossy = bits < 64
    rows = x[lossy]
    levels = (2.0 ** bits[lossy] - 1.0)[:, None]
    lo = rows.min(axis=1, keepdims=True)
    step = np.maximum(rows.max(axis=1, keepdims=True) - lo, 1e-12) / levels
    out[lossy] = np.clip(np.rint((rows - lo) / step), 0, levels) * step + lo
    return out.astype(np.float32)


def reference_gcn(params, graph, feats: np.ndarray) -> np.ndarray:
    """Plain float32 numpy GCN forward (the paper's Table I row):
    ``a_v = sum_{u -> v} h_u``, ``h_v' = act(((a_v + h_v) / (deg_v + 1)) W + b)``
    with ReLU between layers and none after the last."""
    v = graph.num_vertices
    order = np.argsort(graph.receivers, kind="stable")
    senders = np.asarray(graph.senders)[order]
    recv = np.asarray(graph.receivers)[order]
    deg = in_degrees(graph).astype(np.float32)
    starts = np.searchsorted(recv, np.arange(v))
    nonempty = deg > 0
    h = np.asarray(feats, np.float32)
    for i, p in enumerate(params):
        a = np.zeros_like(h)
        a[nonempty] = np.add.reduceat(h[senders], starts[nonempty], axis=0)
        z = (a + h) / (deg + 1.0)[:, None]
        h = z @ np.asarray(p["w"], np.float32) + np.asarray(p["b"], np.float32)
        if i < len(params) - 1:
            h = np.maximum(h, 0.0)
    return h


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def build(seed: int, scale: float):
    import jax

    from repro.gnn import datasets, models

    t0 = time.perf_counter()
    graph = datasets.load("siot", scale=scale, seed=seed)
    classes = int(graph.labels.max()) + 1
    params = models.gnn_init(jax.random.PRNGKey(seed), "gcn",
                             [graph.feature_dim, HIDDEN, classes])
    print(f"graph: siot scale={scale} |V|={graph.num_vertices} "
          f"|E|={graph.num_edges} F={graph.feature_dim}; gcn "
          f"[{graph.feature_dim}, {HIDDEN}, {classes}] "
          f"({time.perf_counter() - t0:.3f}s)", flush=True)
    return graph, params


def make_trace(graph, seed: int, n: int = N_REQUESTS):
    """Poisson trace whose requests carry distinct fresh feature uploads, so
    a response read from the wrong batch slot cannot pass the checks."""
    from repro.api import traces

    base = np.asarray(graph.features, np.float32)

    def features_fn(i, rng):
        noise = rng.standard_normal(base.shape).astype(np.float32)
        return base + np.float32(0.1) * noise

    return traces.poisson(n, RATE, seed=seed, features_fn=features_fn)


def serve(plan, trace, **session_kw):
    """Replay ``trace`` through a fresh server; responses in request order."""
    srv = plan.server(max_batch=MAX_BATCH, **session_kw)
    t0 = time.perf_counter()
    resps = srv.replay(trace)
    seconds = time.perf_counter() - t0
    resps = sorted(resps, key=lambda r: r.request_id)
    return srv, resps, seconds


def references(params, graph, trace) -> list:
    """The numpy GCN of every request's DAQ-quantized upload."""
    deg = in_degrees(graph)
    return [reference_gcn(params, graph, reference_daq(t.features, deg))
            for t in trace]


def check_against_reference(label, resps, refs):
    worst = 0.0
    for r in resps:
        ref = refs[r.request_id]
        err = rel_err(np.asarray(r.embeddings), ref)
        worst = max(worst, err)
        check(np.all(np.isfinite(r.embeddings))
              and r.embeddings.shape == ref.shape and err <= REF_TOL,
              f"{label} request {r.request_id} (batch of {r.batch_size}) "
              f"matches the f32 reference: rel err {err!r} <= {REF_TOL}")
    print(f"{label}: worst rel err vs f32 reference {worst!r}", flush=True)
    cross = min(rel_err(np.asarray(r.embeddings), refs[s])
                for r in resps for s in range(len(refs)) if s != r.request_id)
    check(cross > REF_TOL,
          f"{label}: every response is {cross!r} > {REF_TOL} from the other "
          f"requests' references (a swapped slot would fail)")


def time_served_program(plan, feats: np.ndarray):
    """Device wall time, ending in block_until_ready, of the compiled
    kernel program the server ran for one request ([V, F]) or a stack
    ([B, V, F]); returns (seconds, compiled text).

    The call passes the executor's operands with ``interpret=False``. It
    must hit the jit cache that the replay filled: that shows the server
    ran exactly this compiled program, not interpret mode."""
    import jax.numpy as jnp

    from repro.api import executors
    from repro.gnn.layers import EdgeList
    from repro.kernels import ops

    csr = ops.block_csr_for(plan.graph)
    e = EdgeList.from_graph(plan.graph)
    args = (list(plan.model.params), plan.model.kind,
            jnp.asarray(feats, jnp.float32), e.senders, e.receivers, e.mask,
            csr.blocks, csr.cols, csr.mask)
    fn = executors._kernel_gnn_apply
    entries = fn._cache_size()
    fn(*args, interpret=False).block_until_ready()
    check(fn._cache_size() == entries,
          f"the server already ran the compiled program for "
          f"{tuple(feats.shape)} (jit cache hit with interpret=False)")
    t0 = time.perf_counter()
    fn(*args, interpret=False).block_until_ready()
    seconds = time.perf_counter() - t0
    text = fn.lower(*args, interpret=False).compile().as_text()
    return seconds, text


def one_chip(seed: int, scale: float = 1.0,
             aggregation: str = "auto") -> None:
    import jax

    from repro.api import Engine
    from repro.runtime.bsp import resolve_aggregation

    graph, params = build(seed, scale)
    if scale == 1.0:
        check(graph.num_vertices == 16216,
              f"full-scale SIoT: |V| = {graph.num_vertices}")
    mode = resolve_aggregation(aggregation, "gcn")
    check(mode == "pallas",
          f"resolve_aggregation({aggregation!r}, 'gcn') -> {mode!r}")

    t0 = time.perf_counter()
    plan = Engine((params, "gcn"), executor="single",
                  aggregation=aggregation).compile(graph)
    print(f"Engine.compile: {time.perf_counter() - t0:.3f}s "
          f"(compressor={plan.config.compressor})", flush=True)

    trace = make_trace(graph, seed)
    srv, resps, cold = serve(plan, trace)
    sizes = [r.batch_size for r in resps]
    print(f"pallas replay (includes XLA compile): {cold:.3f}s; "
          f"batch sizes {sizes}", flush=True)
    check(len(resps) == len(trace), f"{len(resps)} responses")
    check(min(sizes) == 1 and max(sizes) > 1,
          "the trace formed singleton and multi-request batches")
    _, _, warm = serve(plan, trace)
    print(f"pallas replay (warm, host collect + device): {warm:.3f}s for "
          f"{len(trace)} requests", flush=True)
    refs = references(params, graph, trace)
    check_against_reference("pallas", resps, refs)

    # Batched vs serial on this device: recorded, not asserted (the
    # bit-identity contract is tested on CPU).
    multi = next(r for r in resps if r.batch_size > 1)
    serial = srv.session.execute(
        srv.session.collect(trace[multi.request_id].features))
    diff = float(np.max(np.abs(np.asarray(multi.embeddings) - serial)))
    print(f"max |batched - serial| on {jax.devices()[0].device_kind}: "
          f"{diff!r} (request {multi.request_id}, batch of "
          f"{multi.batch_size})", flush=True)

    seg_srv, seg_resps, seg_s = serve(plan, trace, aggregation="segment_sum")
    print(f"segment_sum replay (includes XLA compile): {seg_s:.3f}s",
          flush=True)
    check_against_reference("segment_sum", seg_resps, refs)
    gap = max(float(np.max(np.abs(np.asarray(a.embeddings)
                                  - np.asarray(b.embeddings))))
              for a, b in zip(resps, seg_resps))
    print(f"max |pallas - segment_sum|: {gap!r}", flush=True)

    # The served shapes: one request, and the largest batch of the trace.
    b = max(sizes)
    stack = np.stack([np.asarray(srv.session.collect(t.features), np.float32)
                      for t in trace[:b]])
    t1, text1 = time_served_program(plan, stack[0])
    tb, textb = time_served_program(plan, stack)
    print(f"served program device wall time: B=1 {t1 * 1e3:.3f} ms, "
          f"B={b} {tb * 1e3:.3f} ms ({tb / b * 1e3:.3f} ms/request)",
          flush=True)
    check("tpu_custom_call" in text1 and "tpu_custom_call" in textb,
          f"the served programs (B=1 and B={b}) hold compiled Pallas kernels "
          f"(tpu_custom_call)")


def four_chips(seed: int, scale: float = 1.0,
               aggregation: str = "auto") -> None:
    import jax
    from jax.sharding import Mesh

    from repro.api import Engine
    from repro.runtime import bsp

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices >= 4")
    graph, params = build(seed, scale)
    t0 = time.perf_counter()
    plan = Engine((params, "gcn"), cluster=CLUSTER, executor="mesh-bsp",
                  aggregation=aggregation).compile(graph)
    pg = plan.partitioned
    print(f"Engine.compile (mesh-bsp, {CLUSTER}): "
          f"{time.perf_counter() - t0:.3f}s; vertices per fog "
          f"{plan.vertices_per_fog().tolist()}; local tiles "
          f"{pg.local_csr.blocks.shape[1:3]}, halo tiles "
          f"{pg.halo_csr.blocks.shape[1:3]}, halo src rows "
          f"{pg.halo_csr.src_rows}", flush=True)
    check(plan.num_fogs == 4, f"{plan.num_fogs} fogs")
    quant = bsp.resolve_aggregation(aggregation, "gcn",
                                    exchange=plan.config.exchange) == "pallas"
    check(quant and plan.config.compressor == "daq",
          "kernel path with the DAQ-quantized halo wire (dequant_spmm)")

    trace = make_trace(graph, seed, n=6)
    srv, resps, cold = serve(plan, trace)
    sizes = [r.batch_size for r in resps]
    print(f"mesh-bsp replay (includes XLA compile): {cold:.3f}s; batch "
          f"sizes {sizes}", flush=True)
    check(min(sizes) == 1 and max(sizes) > 1,
          "the trace formed singleton and multi-request batches")
    _, base, base_s = serve(plan, trace, executor="single")
    print(f"single-program replay: {base_s:.3f}s", flush=True)
    worst = 0.0
    for r, b in zip(resps, base):
        err = float(np.max(np.abs(np.asarray(r.embeddings)
                                  - np.asarray(b.embeddings))))
        worst = max(worst, err)
        check(np.all(np.isfinite(r.embeddings)) and err <= DAQ_TOL,
              f"mesh request {r.request_id} (batch of {r.batch_size}) "
              f"matches the single program: max abs {err!r} <= {DAQ_TOL}")
    print(f"mesh-bsp vs single program: worst max abs {worst!r}", flush=True)

    # Placement: run the served single-request shard program once more and
    # read where each partition's output lives.
    single = next(r for r in resps if r.batch_size == 1)
    feats = np.asarray(srv.session.collect(trace[single.request_id].features),
                       np.float32)
    mesh = Mesh(np.array(jax.devices()[:pg.n]), ("fog",))
    out = bsp.bsp_apply(list(params), "gcn", pg.with_features(feats), mesh,
                        "fog", plan.config.exchange, aggregation=aggregation,
                        halo_quant=quant)
    owner = {}
    for shard in out.addressable_shards:
        owner[shard.index[0].start] = shard.device
    print("shard -> device: " + ", ".join(
        f"{p}->{owner[p].id}" for p in sorted(owner)), flush=True)
    check(sorted(owner) == list(range(pg.n))
          and len({d.id for d in owner.values()}) == pg.n
          and all(owner[p] == mesh.devices[p] for p in owner),
          f"each of the {pg.n} shards lives on its own device")
    same = np.array_equal(pg.unpermute(np.asarray(out)),
                          np.asarray(single.embeddings))
    check(same, "that program reproduces the served response bit for bit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-fog mesh-bsp path (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.runtime import compile_cache

    cache = compile_cache.enable()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
