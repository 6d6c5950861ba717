"""Executor backends: where a query's numerics actually run.

All backends produce *real* JAX-computed embeddings (quantization error and
exchange semantics are genuine); they differ in how the computation is laid
out and which simulated pipeline prices its latency:

  "sim"       single-program numerics, multi-fog BSP latency accounting —
              the default for laptops/CI (verified numerically identical
              to the mesh path in tests).
  "single"    single-program numerics, single-most-powerful-fog accounting
              (the paper's single-fog baseline).
  "mesh-bsp"  shard_map over a real JAX device mesh, one device per fog
              partition, halo/allgather collectives per layer (§III-E);
              multi-fog accounting.
  "cloud"     single-program numerics, de-facto cloud accounting (full
              WAN upload to a datacenter GPU) — the paper's Fig. 3
              cloud-vs-fog baseline.

Every backend honours the Engine/Session ``aggregation`` knob ("segment_sum"
| "pallas" | "auto"): the single-program backends swap the model's
neighborhood aggregation for the whole-graph block-CSR Pallas kernel, the
mesh backend routes each shard's aggregation through the pre-blocked
local+halo SpMM (and, with a DAQ compressor, ships the halo quantized and
dequantizes inside the fused kernel). ``resolve_aggregation`` in
``runtime.bsp`` defines the fallback/strictness rules.

Micro-batch execution (``run_many``) is natively batched on every backend:
the Server's stacked [B, V, F] feature batch runs in ONE traced call — the
kernel path through the batch-axis grid kernels (``block_spmm_batched`` /
``dequant_spmm_batched``, one fused dispatch for the whole batch), the
segment-sum and GAT paths through one ``jax.vmap`` program, and the mesh
backend through ``bsp.bsp_infer_many`` (one shard_map launch, one
collective per layer for the whole batch). Batched responses are
bit-identical to the serial per-request loop: serial execution runs the
same jitted per-example functions, and vmap / the batched kernels preserve
the per-example op sequence exactly (asserted per executor x model in
tests/test_batched_exec.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import EXECUTORS
from repro.gnn.layers import EdgeList, aggregate_sum, apply_layer_with_sum
from repro.gnn.models import gnn_apply, gnn_apply_layers
from repro.kernels import ops
from repro.kernels.gather_aggregate import (block_spmm, block_spmm_batched,
                                            padded_feature_dim)
from repro.runtime import bsp, tracing

#: model kinds the incremental frontier path supports: their per-layer
#: aggregation is a static SUM over fixed adjacency, so a row subset can
#: be recomputed from sub-edges (GAT re-weights edges per layer from all
#: rows' values, so a dirty-row restriction is unsound).
FRONTIER_KINDS = ("gcn", "sage")


def _as_stack(feats: Union[np.ndarray, Sequence[np.ndarray]]) -> np.ndarray:
    """Coerce a micro-batch (list of [V, F] arrays or an already stacked
    [B, V, F] array) to one stacked float32 array."""
    if isinstance(feats, np.ndarray) and feats.ndim == 3:
        return np.asarray(feats, np.float32)
    return np.stack([np.asarray(f, np.float32) for f in feats])


@dataclasses.dataclass(frozen=True)
class ExecutorBackend:
    """Base entry for the EXECUTORS registry.

    ``pipeline`` names the ``simulation.simulate`` accounting pipeline
    ("multi", "single" or "cloud"); ``run`` returns [V, D] embeddings in
    original vertex order. ``aggregation`` is the resolved Engine/Session
    knob (see ``bsp.resolve_aggregation``).
    """
    name: str
    pipeline: str

    #: True for backends whose kernel path reads the per-shard block-CSR
    #: operands of the PartitionedGraph (built on demand).
    needs_block_shards = False

    def check(self, plan) -> None:
        """Fail fast (helpful error) if this backend cannot run the plan."""

    def wire_format(self, plan, exchange: str, aggregation: str):
        """(dtype_bytes, row_overhead_bytes) of the per-sync halo payload."""
        return (4, 0)

    def run(self, plan, feats: np.ndarray, assignment: np.ndarray,
            pg: bsp.PartitionedGraph, exchange: str,
            aggregation: str = "segment_sum") -> np.ndarray:
        raise NotImplementedError

    def run_many(self, plan,
                 feats: Union[np.ndarray, Sequence[np.ndarray]],
                 assignment: np.ndarray, pg: bsp.PartitionedGraph,
                 exchange: str,
                 aggregation: str = "segment_sum") -> List[np.ndarray]:
        """One executor run over a micro-batch of feature sets.

        ``feats`` is either a stacked [B, V, F] array (what the Server's
        micro-batcher hands over) or a sequence of [V, F] arrays. The
        base implementation serves each set through ``run`` back-to-back;
        every registered backend overrides it with a natively batched
        single-dispatch path whose per-request results are bit-identical
        to this serial loop (the batching win is additionally priced by
        ``simulation.simulate(batch_size=B)``).
        """
        return [self.run(plan, f, assignment, pg, exchange,
                         aggregation=aggregation)
                for f in _as_stack(feats)]

    # -- incremental (frontier) execution ------------------------------------

    #: numerics family tag for the activation cache: values cached under
    #: one family must not be merged into another's recompute ("single"
    #: covers sim/single/cloud, which share one jitted program).
    frontier_family = "single"

    def supports_frontier(self, plan, aggregation: str) -> bool:
        """Whether ``run_frontier``/``run_layers`` exist for this plan."""
        return False

    def run_layers(self, plan, feats, assignment, pg, exchange,
                   aggregation: str = "segment_sum") -> List[np.ndarray]:
        """Full pass that also returns every layer's activations.

        ``feats`` is [V, F] (returns K arrays [V, F_l]) or a stacked
        [B, V, F] micro-batch (returns K arrays [B, V, F_l]); the last
        entry is the plain ``run``/``run_many`` output, bit for bit.
        """
        raise NotImplementedError

    def run_frontier(self, plan, feats, assignment, pg, exchange,
                     aggregation, rows_per_layer, cached_layers):
        """Incremental pass: recompute only ``rows_per_layer[l]`` per
        layer and scatter-merge into ``cached_layers``. Returns
        ``(embeddings, merged_layers)`` where embeddings is [V, D] (or a
        list of [V, D] for a stacked ``feats``) bit-identical to a full
        recompute, and merged_layers is the new cache state.
        """
        raise NotImplementedError

    # -- stale-tolerant halo serving (exchange="halo_async") -----------------

    def supports_stale_halo(self, plan, aggregation: str) -> bool:
        """Whether this backend can replay recorded halo tables
        (``run_stale``/``run_stale_many``). Only the mesh backend has a
        real exchange to skip; single-program backends serve stale
        requests through their ordinary path (the Session still does the
        version/staleness accounting)."""
        return False

    def run_stale(self, plan, feats, assignment, pg,
                  halo_tables, aggregation: str = "segment_sum"):
        """Serve one query replaying ``halo_tables`` (the per-layer
        boundary-row tables of an earlier fresh pass) instead of running
        the per-layer exchange. Local rows use the CURRENT ``feats``;
        only cross-partition reads are stale."""
        raise NotImplementedError

    def run_stale_many(self, plan, feats, assignment, pg,
                       halo_tables, aggregation: str = "segment_sum"):
        raise NotImplementedError


@functools.partial(jax.jit, static_argnames=("kind",))
def _jit_gnn_apply(params, kind, h, senders, receivers, mask):
    """Jitted per-example K-layer forward (segment-sum aggregation).

    The serial ``run`` path uses this (rather than tracing ``gnn_apply``
    eagerly) so serial and batched execution share one compiled op
    sequence: jit-vs-eager differs in the last float bits for some layer
    stacks (GAT's attention softmax), while ``jax.vmap`` of a jitted
    function is bit-identical per example.
    """
    edges = EdgeList(senders, receivers, mask, h.shape[-2])
    return gnn_apply(params, kind, h, edges)


@functools.partial(jax.jit, static_argnames=("kind",))
def _batched_gnn_apply(params, kind, stacked, senders, receivers, mask):
    """vmap of the K-layer forward over a [B, V, F] feature stack.

    One traced call per (graph, batch-size) instead of B dispatches; the
    per-example computation is the same op sequence as
    ``_jit_gnn_apply``, so results are bit-identical to the serial loop
    for every kind — including GAT, whose per-layer attention re-weighting
    rides this vmapped edge-weighted path (asserted in
    tests/test_batched_exec.py and tests/test_updates.py).
    """
    edges = EdgeList(senders, receivers, mask, stacked.shape[-2])
    return jax.vmap(lambda h: gnn_apply(params, kind, h, edges))(stacked)


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def _kernel_gnn_apply(params, kind, h, senders, receivers, mask,
                      blocks, cols, cmask, *, interpret):
    """K-layer forward with block-CSR Pallas aggregation, single or stacked.

    ``h`` is one [V, F] feature table or a stacked [B, V, F] micro-batch.
    Per layer, the neighbor sum runs as ONE fused SpMM dispatch —
    ``block_spmm`` for a single example, ``block_spmm_batched`` (batch
    grid axis + scalar-prefetched column table) for a stack — and the
    dense layer update then runs per-example (under ``jax.vmap`` for the
    stacked case), which keeps batched results bit-identical to serial
    ones: the batched kernel preserves the per-(row-block, feature-tile)
    arithmetic of the unbatched kernel, and vmap preserves the dense op
    sequence. GCN/SAGE only (GAT re-weights edges per layer and cannot be
    pre-blocked; ``resolve_aggregation`` rejects it upstream).
    """
    v = h.shape[-2]
    edges = EdgeList(senders, receivers, mask, v)
    padded_v = blocks.shape[0] * blocks.shape[-1]

    def spmm(src):
        f = src.shape[-1]
        pad = ((0, padded_v - v), (0, padded_feature_dim(f) - f))
        if src.ndim == 3:
            out = block_spmm_batched(
                blocks, cols, cmask,
                jnp.pad(src.astype(jnp.float32), ((0, 0),) + pad),
                interpret=interpret)
            return out[:, :v, :f]
        out = block_spmm(blocks, cols, cmask,
                         jnp.pad(src.astype(jnp.float32), pad),
                         interpret=interpret)
        return out[:v, :f]

    n = len(params)
    for i, p in enumerate(params):
        # Fused (batched) SpMM dispatch, then the shared dense tail.
        h = apply_layer_with_sum(kind, p, h, edges, spmm(h), last=i == n - 1)
    return h


@functools.partial(jax.jit, static_argnames=("kind",))
def _jit_gnn_capture(params, kind, h, senders, receivers, mask):
    """``_jit_gnn_apply`` returning every layer (same traced program
    modulo dead-code elimination — see ``gnn_apply_layers``)."""
    edges = EdgeList(senders, receivers, mask, h.shape[-2])
    return gnn_apply_layers(params, kind, h, edges)


@functools.partial(jax.jit, static_argnames=("kind",))
def _batched_gnn_capture(params, kind, stacked, senders, receivers, mask):
    """``_batched_gnn_apply`` returning every layer ([B, V, F_l] each)."""
    edges = EdgeList(senders, receivers, mask, stacked.shape[-2])
    return jax.vmap(lambda h: gnn_apply_layers(params, kind, h, edges))(
        stacked)


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def _kernel_gnn_capture(params, kind, h, senders, receivers, mask,
                        blocks, cols, cmask, *, interpret):
    """``_kernel_gnn_apply`` returning every layer, single or stacked."""
    v = h.shape[-2]
    edges = EdgeList(senders, receivers, mask, v)
    padded_v = blocks.shape[0] * blocks.shape[-1]

    def spmm(src):
        f = src.shape[-1]
        pad = ((0, padded_v - v), (0, padded_feature_dim(f) - f))
        if src.ndim == 3:
            out = block_spmm_batched(
                blocks, cols, cmask,
                jnp.pad(src.astype(jnp.float32), ((0, 0),) + pad),
                interpret=interpret)
            return out[:, :v, :f]
        out = block_spmm(blocks, cols, cmask,
                         jnp.pad(src.astype(jnp.float32), pad),
                         interpret=interpret)
        return out[:v, :f]

    n = len(params)
    outs = []
    for i, p in enumerate(params):
        h = apply_layer_with_sum(kind, p, h, edges, spmm(h), last=i == n - 1)
        outs.append(h)
    return outs


def _bucket(n: int, lo: int = 8) -> int:
    """Next power of two >= max(n, lo): bounds the jit shape churn of the
    per-layer frontier programs to O(log V) specializations."""
    b = lo
    while b < n:
        b *= 2
    return b


def _segment_frontier_operands(graph, rows: np.ndarray):
    """Static-shape operands for one layer's sub-edge recompute.

    ``rows`` are the layer's dirty vertices. The row list is padded to a
    bucket with ``V`` — an out-of-bounds id the scatter-merge drops — and
    the edges *into* dirty rows are extracted in original edge order
    (the bit-identity of the per-row segment sums rests on that), with
    receivers compacted to row positions. Padding edges carry mask 0 and
    point at the last row slot, which the ``len(rows) + 1`` bucket floor
    guarantees is a padding slot, so their +0.0 never touches a real row.
    """
    v = graph.num_vertices
    rows = np.asarray(rows, np.int64)
    r_pad = _bucket(len(rows) + 1)
    rows_p = np.full(r_pad, v, np.int64)
    rows_p[:len(rows)] = rows
    comp = np.zeros(v, np.int64)
    comp[rows] = np.arange(len(rows))
    dirty = np.zeros(v, bool)
    dirty[rows] = True
    send = np.asarray(graph.senders, np.int64)
    recv = np.asarray(graph.receivers, np.int64)
    sel = np.flatnonzero(dirty[recv])
    e_pad = _bucket(len(sel))
    sub_s = np.zeros(e_pad, np.int32)
    sub_r = np.full(e_pad, r_pad - 1, np.int32)
    sub_m = np.zeros(e_pad, np.float32)
    sub_s[:len(sel)] = send[sel]
    sub_r[:len(sel)] = comp[recv[sel]]
    sub_m[:len(sel)] = 1.0
    return (jnp.asarray(rows_p), jnp.asarray(sub_s), jnp.asarray(sub_r),
            jnp.asarray(sub_m))


def _kernel_frontier_operands(graph, rows: np.ndarray, block: int):
    """Row-block-granular operands for the Pallas frontier path.

    The dirty rows are widened to whole 128-row blocks (the kernel's
    launch unit); every row of a selected block is recomputed and merged
    — bit-safe, since a clean row in a dirty block sees exactly its full
    operands. The block list is padded to a bucket with block 0; padding
    slots' row ids are set to ``V`` so their (duplicate) outputs drop at
    the scatter. Degrees and the dense tail then ride the same sub-edge
    machinery as the segment path, keyed by the widened row set.
    """
    v = graph.num_vertices
    rows = np.asarray(rows, np.int64)
    sel = np.unique(rows // block)
    s_pad = _bucket(len(sel) + 1, lo=1)
    sel_p = np.zeros(s_pad, np.int64)
    sel_p[:len(sel)] = sel
    rows_k = (sel_p[:, None] * block + np.arange(block)).reshape(-1)
    rows_k[len(sel) * block:] = v          # padding blocks: all dropped
    real = rows_k[rows_k < v]              # in-graph rows of real blocks
    r_pad = rows_k.shape[0]
    comp = np.zeros(v, np.int64)
    comp[real] = np.flatnonzero(rows_k < v)
    dirty = np.zeros(v, bool)
    dirty[real] = True
    send = np.asarray(graph.senders, np.int64)
    recv = np.asarray(graph.receivers, np.int64)
    e_sel = np.flatnonzero(dirty[recv])
    e_pad = _bucket(len(e_sel))
    sub_s = np.zeros(e_pad, np.int32)
    sub_r = np.full(e_pad, r_pad - 1, np.int32)
    sub_m = np.zeros(e_pad, np.float32)
    sub_s[:len(e_sel)] = send[e_sel]
    sub_r[:len(e_sel)] = comp[recv[e_sel]]
    sub_m[:len(e_sel)] = 1.0
    # rows_k[-1] is always a padding slot (s_pad >= len(sel) + 1), so the
    # padded sub-edges above never land on a real row.
    return (jnp.asarray(rows_k), jnp.asarray(sub_s), jnp.asarray(sub_r),
            jnp.asarray(sub_m), jnp.asarray(sel_p))


def _segment_frontier_tail(p, kind, h_full, cached_out, rows, sub_s, sub_r,
                           sub_m, last):
    """One incremental layer: sub-edge segment aggregation over the dirty
    rows, the shared dense tail on the gathered rows, scatter-merge into
    the cached table. Out-of-range row ids (padding) clamp on gather and
    drop on scatter."""
    edges = EdgeList(sub_s, sub_r, sub_m, rows.shape[0])
    a = aggregate_sum(h_full, edges)
    out = apply_layer_with_sum(kind, p, h_full[rows], edges, a, last=last)
    return cached_out.at[rows].set(out, mode="drop")


@functools.partial(jax.jit, static_argnames=("kind", "last"))
def _segment_frontier_layer(p, kind, h_full, cached_out, rows, sub_s,
                            sub_r, sub_m, *, last):
    return _segment_frontier_tail(p, kind, h_full, cached_out, rows,
                                  sub_s, sub_r, sub_m, last)


@functools.partial(jax.jit, static_argnames=("kind", "last"))
def _segment_frontier_layer_many(p, kind, h_stack, cached_out, rows, sub_s,
                                 sub_r, sub_m, *, last):
    """vmap of the incremental layer over a stacked micro-batch sharing
    one (unioned) frontier; the cached table broadcasts."""
    return jax.vmap(lambda hf: _segment_frontier_tail(
        p, kind, hf, cached_out, rows, sub_s, sub_r, sub_m, last))(h_stack)


def _kernel_frontier_sum(h_full, sel, blocks, cols, cmask, interpret):
    """Neighbor sums for the selected row blocks: ``block_spmm`` over the
    gathered tile subset — bit-identical to the corresponding row slice
    of the full launch (same per-(row-block, f-tile) accumulation)."""
    v, f = h_full.shape[-2:]
    block = blocks.shape[-1]
    padded_v = blocks.shape[0] * block
    pad = ((0, padded_v - v), (0, padded_feature_dim(f) - f))
    sub = (blocks[sel], cols[sel], cmask[sel])
    if h_full.ndim == 3:
        out = block_spmm_batched(
            *sub, jnp.pad(h_full.astype(jnp.float32), ((0, 0),) + pad),
            interpret=interpret)
        return out[..., :f]
    out = block_spmm(*sub, jnp.pad(h_full.astype(jnp.float32), pad),
                     interpret=interpret)
    return out[:, :f]


@functools.partial(jax.jit, static_argnames=("kind", "last", "interpret"))
def _kernel_frontier_layer(p, kind, h_full, cached_out, rows, sub_s, sub_r,
                           sub_m, sel, blocks, cols, cmask, *, last,
                           interpret):
    a = _kernel_frontier_sum(h_full, sel, blocks, cols, cmask, interpret)
    edges = EdgeList(sub_s, sub_r, sub_m, rows.shape[0])
    out = apply_layer_with_sum(kind, p, h_full[rows], edges, a, last=last)
    return cached_out.at[rows].set(out, mode="drop")


@functools.partial(jax.jit, static_argnames=("kind", "last", "interpret"))
def _kernel_frontier_layer_many(p, kind, h_stack, cached_out, rows, sub_s,
                                sub_r, sub_m, sel, blocks, cols, cmask, *,
                                last, interpret):
    a = _kernel_frontier_sum(h_stack, sel, blocks, cols, cmask, interpret)
    edges = EdgeList(sub_s, sub_r, sub_m, rows.shape[0])
    out = apply_layer_with_sum(kind, p, h_stack[:, rows], edges, a,
                               last=last)
    return jax.vmap(lambda o: cached_out.at[rows].set(o, mode="drop"))(out)


class _SingleProgram(ExecutorBackend):
    def _apply(self, plan, feats, aggregation: str) -> jnp.ndarray:
        """Upload ``feats`` = [V, F] or [B, V, F] with the edge operands
        and dispatch one traced call over them."""
        with tracing.span("execute.dispatch") as span:
            h = jnp.asarray(feats, jnp.float32)
            # Single-program layout: no cross-fog exchange is involved, so
            # the kernel path only depends on the model kind.
            mode = bsp.resolve_aggregation(aggregation, plan.model.kind)
            params = list(plan.model.params)
            edges = EdgeList.from_graph(plan.graph)
            span.set_metadata(upload_bytes=(
                (0 if isinstance(feats, jax.Array) else h.nbytes)
                + edges.senders.nbytes + edges.receivers.nbytes
                + edges.mask.nbytes))
            if mode == "pallas":
                csr = ops.block_csr_for(plan.graph)
                return _kernel_gnn_apply(
                    params, plan.model.kind, h, edges.senders,
                    edges.receivers, edges.mask, csr.blocks, csr.cols,
                    csr.mask, interpret=jax.default_backend() != "tpu")
            if h.ndim == 3:
                return _batched_gnn_apply(params, plan.model.kind, h,
                                          edges.senders, edges.receivers,
                                          edges.mask)
            return _jit_gnn_apply(params, plan.model.kind, h, edges.senders,
                                  edges.receivers, edges.mask)

    def run(self, plan, feats, assignment, pg, exchange,
            aggregation="segment_sum"):
        out = bsp.wait(self._apply(plan, feats, aggregation))
        with tracing.span("execute.download", download_bytes=out.nbytes):
            return np.asarray(out)

    def run_many(self, plan, feats, assignment, pg, exchange,
                 aggregation="segment_sum"):
        """Batched fast path: one traced call over the stacked micro-batch
        instead of B dispatches — the batch-axis Pallas kernels for the
        GCN/SAGE kernel path, ``jax.vmap`` for segment-sum and GAT.
        Singleton batches take the serial path (B=1 reproduces the
        single-query numbers and timings exactly).
        """
        stacked = _as_stack(feats)
        if stacked.shape[0] <= 1:
            return super().run_many(plan, stacked, assignment, pg,
                                    exchange, aggregation=aggregation)
        out = self._apply(plan, stacked, aggregation)
        # Slice the examples apart right behind the call, before waiting:
        # waiting first and slicing after read 3.6 % fewer requests/s on a
        # TPU v5e (closed-loop batches of 8).
        parts = bsp.wait(list(out))
        with tracing.span("execute.download", download_bytes=out.nbytes):
            return [np.asarray(p) for p in parts]

    def supports_frontier(self, plan, aggregation):
        return plan.model.kind in FRONTIER_KINDS

    def run_layers(self, plan, feats, assignment, pg, exchange,
                   aggregation="segment_sum"):
        h = jnp.asarray(feats, jnp.float32)
        mode = bsp.resolve_aggregation(aggregation, plan.model.kind)
        params = list(plan.model.params)
        edges = EdgeList.from_graph(plan.graph)
        if mode == "pallas":
            csr = ops.block_csr_for(plan.graph)
            outs = _kernel_gnn_capture(
                params, plan.model.kind, h, edges.senders, edges.receivers,
                edges.mask, csr.blocks, csr.cols, csr.mask,
                interpret=jax.default_backend() != "tpu")
        elif h.ndim == 3:
            outs = _batched_gnn_capture(params, plan.model.kind, h,
                                        edges.senders, edges.receivers,
                                        edges.mask)
        else:
            outs = _jit_gnn_capture(params, plan.model.kind, h,
                                    edges.senders, edges.receivers,
                                    edges.mask)
        return [np.asarray(o) for o in outs]

    def run_frontier(self, plan, feats, assignment, pg, exchange,
                     aggregation, rows_per_layer, cached_layers):
        mode = bsp.resolve_aggregation(aggregation, plan.model.kind)
        kind = plan.model.kind
        params = list(plan.model.params)
        g = plan.graph
        h = jnp.asarray(feats, jnp.float32)
        stacked = h.ndim == 3
        csr = ops.block_csr_for(g) if mode == "pallas" else None
        interp = jax.default_backend() != "tpu"
        n = len(params)
        merged = []
        for i, p in enumerate(params):
            cached = jnp.asarray(cached_layers[i], jnp.float32)
            last = i == n - 1
            if mode == "pallas":
                rows, sub_s, sub_r, sub_m, sel = _kernel_frontier_operands(
                    g, rows_per_layer[i], int(csr.blocks.shape[-1]))
                fl = (_kernel_frontier_layer_many if stacked
                      else _kernel_frontier_layer)
                h = fl(p, kind, h, cached, rows, sub_s, sub_r, sub_m, sel,
                       csr.blocks, csr.cols, csr.mask, last=last,
                       interpret=interp)
            else:
                rows, sub_s, sub_r, sub_m = _segment_frontier_operands(
                    g, rows_per_layer[i])
                fl = (_segment_frontier_layer_many if stacked
                      else _segment_frontier_layer)
                h = fl(p, kind, h, cached, rows, sub_s, sub_r, sub_m,
                       last=last)
            merged.append(np.asarray(h))
        emb = merged[-1]
        if stacked:
            return [e for e in emb], merged
        return emb, merged


class _MeshBsp(ExecutorBackend):
    #: this backend aggregates over PartitionedGraph.local_csr/halo_csr
    #: when the kernel path is active (Engine/Session build them lazily).
    needs_block_shards = True

    def check(self, plan) -> None:
        n = plan.num_fogs
        have = len(jax.devices())
        if have < n:
            raise RuntimeError(
                f"executor 'mesh-bsp' needs {n} JAX devices (one per fog "
                f"partition), have {have} — run under XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n}, or switch "
                f"the engine's executor knob to 'sim'")

    @staticmethod
    def _halo_quant(plan, exchange: str, aggregation: str) -> bool:
        """DAQ plans fuse wire dequantization into the halo SpMM (kernel
        path only): boundary rows cross the collective quantized."""
        return (bsp.resolve_aggregation(aggregation, plan.model.kind,
                                        exchange=exchange) == "pallas"
                and plan.config.compressor.startswith("daq"))

    def wire_format(self, plan, exchange, aggregation):
        if self._halo_quant(plan, exchange, aggregation):
            return (1, 8)   # uint8 codes + f32 (scale, min) per row
        return (4, 0)

    def run(self, plan, feats, assignment, pg, exchange,
            aggregation="segment_sum"):
        g = dataclasses.replace(plan.graph, features=feats)
        return bsp.bsp_infer(
            list(plan.model.params), plan.model.kind, g, assignment,
            exchange=exchange, aggregation=aggregation,
            halo_quant=self._halo_quant(plan, exchange, aggregation), pg=pg)

    def run_many(self, plan, feats, assignment, pg, exchange,
                 aggregation="segment_sum"):
        """One shard_map launch for the whole micro-batch: the stacked
        [B, V, F] features become an [n, B, P, F] partition table and the
        per-layer halo collective ships every example's boundary rows in
        one all_gather (see ``bsp.bsp_apply_many``). Bit-identical to the
        serial per-request loop; singleton batches take the serial path.
        """
        stacked = _as_stack(feats)
        if stacked.shape[0] <= 1:
            return super().run_many(plan, stacked, assignment, pg,
                                    exchange, aggregation=aggregation)
        out = bsp.bsp_infer_many(
            list(plan.model.params), plan.model.kind, stacked, pg,
            exchange=exchange, aggregation=aggregation,
            halo_quant=self._halo_quant(plan, exchange, aggregation))
        return [np.asarray(o) for o in out]

    #: mesh numerics (per-shard layouts, halo accumulation order) differ
    #: from the single program's in the last float bits, so cached layers
    #: are tagged with a distinct family and never cross-merged.
    frontier_family = "mesh"

    def supports_frontier(self, plan, aggregation):
        return plan.model.kind in FRONTIER_KINDS

    def run_layers(self, plan, feats, assignment, pg, exchange,
                   aggregation="segment_sum"):
        feats = np.asarray(feats, np.float32)
        hq = self._halo_quant(plan, exchange, aggregation)
        if feats.ndim == 3:
            return bsp.bsp_infer_capture_many(
                list(plan.model.params), plan.model.kind, feats, pg,
                exchange=exchange, aggregation=aggregation, halo_quant=hq)
        g = dataclasses.replace(plan.graph, features=feats)
        return bsp.bsp_infer_capture(
            list(plan.model.params), plan.model.kind, g, assignment,
            exchange=exchange, aggregation=aggregation, halo_quant=hq,
            pg=pg)

    def run_frontier(self, plan, feats, assignment, pg, exchange,
                     aggregation, rows_per_layer, cached_layers):
        feats = np.asarray(feats, np.float32)
        hq = self._halo_quant(plan, exchange, aggregation)
        if feats.ndim == 3:
            merged = bsp.bsp_infer_frontier_many(
                list(plan.model.params), plan.model.kind, feats, pg,
                rows_per_layer, cached_layers, exchange=exchange,
                aggregation=aggregation, halo_quant=hq)
            return [e for e in merged[-1]], merged
        merged = bsp.bsp_infer_frontier(
            list(plan.model.params), plan.model.kind, feats, pg,
            rows_per_layer, cached_layers, exchange=exchange,
            aggregation=aggregation, halo_quant=hq)
        return merged[-1], merged

    def supports_stale_halo(self, plan, aggregation):
        return True

    def run_stale(self, plan, feats, assignment, pg, halo_tables,
                  aggregation="segment_sum"):
        """Replay recorded halo tables through the "stale" shard_map
        program (no per-layer collective; see ``bsp.bsp_infer_stale``)."""
        return bsp.bsp_infer_stale(
            list(plan.model.params), plan.model.kind,
            np.asarray(feats, np.float32), pg, halo_tables,
            aggregation=aggregation)

    def run_stale_many(self, plan, feats, assignment, pg, halo_tables,
                       aggregation="segment_sum"):
        stacked = _as_stack(feats)
        out = bsp.bsp_infer_stale_many(
            list(plan.model.params), plan.model.kind, stacked, pg,
            halo_tables, aggregation=aggregation)
        return [np.asarray(o) for o in out]


EXECUTORS.register("sim", _SingleProgram("sim", "multi"))
EXECUTORS.register("single", _SingleProgram("single", "single"))
EXECUTORS.register("mesh-bsp", _MeshBsp("mesh-bsp", "multi"))
EXECUTORS.register("cloud", _SingleProgram("cloud", "cloud"))
