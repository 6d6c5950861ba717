"""Distributed BSP GNN inference runtime (paper §III-E) on a JAX mesh.

The paper's runtime: each fog holds a vertex partition; every GNN layer runs
Aggregate/Update over local vertices, pulling neighbor activations from
other fogs in a Bulk-Synchronous-Parallel step (K syncs for K layers).

TPU/JAX adaptation: fogs = devices along a ``fog`` mesh axis, executed with
``shard_map``. The per-layer cross-fog exchange supports two strategies:

  * ``"allgather"``  — all_gather the full [P, F] partition activations
    (straw-man exchange; O(n·P·F) bytes per device per layer).
  * ``"halo"``       — all_gather only the *boundary rows* (vertices that any
    other partition reads), packed into a [B, F] buffer (B = max boundary
    size). This is the paper's "exchange vertices data when needed",
    and the §Perf knob for the collective roofline term.
  * ``"halo_async"`` — the stale-tolerant variant for WAN-separated fleet
    sites: a *fresh* serve runs the exact ``"halo"`` program (same cached
    shard_map program, bit for bit) while the per-layer gathered halo
    tables are recorded host-side (``build_halo_tables``); a *stale* serve
    (``bsp_infer_stale`` / ``bsp_infer_stale_many``) replays those tables
    as replicated operands instead of stalling the superstep on a live
    collective — local rows always read CURRENT features, only
    cross-partition reads may be up to ``staleness_bound`` versions old.

All synchronous modes produce identical results; tests assert equality
against single-device execution. Per-partition buffers are padded to common
static shapes so the whole computation jits once.

Shard-local aggregation runs on one of two numerically equivalent paths,
selected by the ``aggregation`` knob (plumbed from ``Engine`` through the
EXECUTORS entries):

  * ``"segment_sum"`` — gather + ``jax.ops.segment_sum`` over the padded
    COO edge list (the portable baseline).
  * ``"pallas"``      — the block-CSR Pallas kernels: each shard's
    adjacency is pre-blocked at ``build_partitioned`` time into *two*
    ELL-block-CSR operands — one over the local slot space and one over
    the gathered halo table — and the per-layer aggregate becomes
    ``block_spmm(local) + block_spmm(halo)`` (MXU matmuls instead of
    scatter-adds). When the serving plan compresses uploads with DAQ, the
    halo rows additionally cross the collective *quantized* (uint8 codes
    + per-row scale/min) and are dequantized inside the fused
    ``dequant_spmm`` kernel, shrinking the BSP wire term by ~4x.
  * ``"auto"``        — ``"pallas"`` wherever it is supported *and* the
    program runs on a real TPU backend (off-TPU the kernels execute in
    interpret mode, which is only useful for correctness); otherwise
    ``"segment_sum"``.

The kernel path supports the sum/mean aggregations of GCN and GraphSAGE
under the ``"halo"`` exchange; GAT's attention-weighted aggregation and the
``"allgather"`` straw-man stay on ``segment_sum`` (requesting ``"pallas"``
for those raises, ``"auto"`` silently falls back).

Buffer conventions: all feature math is float32; padded vertex rows, edge
slots, boundary rows and ELL tiles are zero-filled and masked (``*_mask``
arrays, 1.0 = real), so every code path may blindly multiply-accumulate.

Micro-batches run through ``bsp_apply_many`` / ``bsp_infer_many``: a
stacked [B, V, F] feature batch becomes one [n, B, P, F] partition table
(``PartitionedGraph.feature_stack``) and ONE shard_map launch serves the
whole batch — one halo collective per layer, the batch-grid Pallas
kernels on the GCN/SAGE kernel path, vmapped per-example layers on the
segment-sum/GAT path — with every example bit-identical to the serial
``bsp_apply`` (see docs/architecture.md §5 "Batched execution").
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.registry import EXCHANGES
from repro.gnn.graph import Graph
from repro.gnn.layers import (EdgeList, LAYER_FNS, apply_layer_with_sum,
                              masked_degree)
from repro.kernels.daq_dequant import dequant_spmm, dequant_spmm_batched
from repro.kernels.gather_aggregate import (BLOCK, block_spmm,
                                            block_spmm_batched,
                                            build_block_csr,
                                            padded_feature_dim)
from repro.runtime import tracing

#: legal values of the Engine/Session ``aggregation`` knob.
AGGREGATIONS = ("segment_sum", "pallas", "auto")

#: GNN kinds whose neighborhood aggregation is a static (weighted) sum and
#: can therefore be pre-blocked into an SpMM. GAT re-weights edges per layer
#: with attention, so its aggregation stays on segment_sum.
KERNEL_KINDS = ("gcn", "sage")


def resolve_aggregation(mode: str, kind: str, *,
                        exchange: Optional[str] = None) -> str:
    """Resolve the ``aggregation`` knob to a concrete path for one run.

    ``exchange=None`` means "no cross-fog exchange involved" (the
    single-program executors). ``"pallas"`` is strict — unsupported
    combinations raise; ``"auto"`` degrades to ``"segment_sum"`` off-TPU
    or wherever the kernels do not apply.
    """
    if mode not in AGGREGATIONS:
        raise ValueError(f"unknown aggregation {mode!r}; available: "
                         f"{', '.join(AGGREGATIONS)}")
    # halo_async serves (fresh or stale) read the same halo-table row space
    # the block-CSR shards are built over, so the kernel path applies.
    supported = (kind in KERNEL_KINDS
                 and exchange in (None, "halo", "halo_async"))
    if mode == "pallas":
        if kind not in KERNEL_KINDS:
            raise ValueError(
                f"aggregation='pallas' supports kinds {KERNEL_KINDS} "
                f"(static-sum aggregation); {kind!r} re-weights edges per "
                f"layer — use aggregation='segment_sum' or 'auto'")
        if exchange is not None and exchange not in ("halo", "halo_async"):
            raise ValueError(
                "aggregation='pallas' requires the 'halo' exchange (the "
                f"block-CSR shards are built over the halo table), got "
                f"exchange={exchange!r}")
        return "pallas"
    if mode == "segment_sum":
        return "segment_sum"
    on_tpu = jax.default_backend() == "tpu"
    return "pallas" if (supported and on_tpu) else "segment_sum"


def _wire_exchange(exchange: str) -> str:
    """The synchronous program behind an exchange mode.  ``halo_async``'s
    fresh path IS the ``halo`` program (same ``_program_key``, same cached
    shard_map program), which is what makes its ``staleness_bound=0`` mode
    bit-identical to the synchronous exchange by construction."""
    return "halo" if exchange == "halo_async" else exchange


@dataclasses.dataclass
class BlockShardCsr:
    """Per-shard ELL-block-CSR adjacency, stacked over all partitions.

    One entry per sender index space: tile ``[p, i, m]`` scatters source
    rows ``cols[p, i, m]*B .. +B`` of that space into local output rows
    ``i*B .. +B`` of partition ``p``. ``mask`` is 1.0 for real tiles, 0.0
    for ELL padding (all-zero tiles pointing at source block 0). All
    partitions share one ``M`` (max tiles per row-block across shards).
    """
    blocks: np.ndarray   # f32[n, VB, M, B, B]
    cols: np.ndarray     # i32[n, VB, M]
    mask: np.ndarray     # f32[n, VB, M]
    src_rows: int        # padded source-table rows (multiple of B)
    out_rows: int        # VB * B (>= slots; slice back to slots)


def _stack_block_shards(edge_sets, out_size: int, src_size: int,
                        block: int = BLOCK,
                        prev: Optional[BlockShardCsr] = None,
                        clean: Optional[np.ndarray] = None) -> BlockShardCsr:
    """Build one block-CSR per partition and ELL-pad them to a common M.

    ``prev``/``clean`` enable the dirty-shard rebuild: for partitions with
    ``clean[p]`` True, the (expensive) ``build_block_csr`` call is skipped
    and shard ``p``'s tiles are sliced out of ``prev`` instead.  Reuse is
    only legal when the stacked layout is compatible (same partition count,
    padded output rows and padded source rows); otherwise everything is
    rebuilt.  Real tiles are packed first per row-block, so slicing the
    first ``M_p`` tile slots of a clean shard carries them all.
    """
    vb = -(-out_size // block)
    n = len(edge_sets)
    src_rows = int(-(-src_size // block) * block)
    reuse = (prev is not None and clean is not None
             and prev.blocks.shape[0] == n
             and prev.out_rows == vb * block and prev.src_rows == src_rows)
    built = {}
    per_shard_m = np.zeros(n, np.int64)
    for p, (s, r) in enumerate(edge_sets):
        if reuse and clean[p]:
            per_shard_m[p] = max(1, int(prev.mask[p].sum(axis=1).max()))
        else:
            built[p] = build_block_csr(s, r, out_size, block)
            per_shard_m[p] = built[p][0].shape[1]
    m = int(per_shard_m.max())
    blocks = np.zeros((n, vb, m, block, block), np.float32)
    cols = np.zeros((n, vb, m), np.int32)
    mask = np.zeros((n, vb, m), np.float32)
    for p in range(n):
        mp = int(per_shard_m[p])
        if p in built:
            b, c, k, _ = built[p]
        else:
            b, c, k = (prev.blocks[p, :, :mp], prev.cols[p, :, :mp],
                       prev.mask[p, :, :mp])
        blocks[p, :, :mp] = b
        cols[p, :, :mp] = c
        mask[p, :, :mp] = k
    # The SpMM kernels index the source table by block with no bounds
    # check — guarantee here (where cols are concrete) that a table padded
    # to src_rows covers every referenced column block.
    assert int(cols.max()) < src_rows // block, (cols.max(), src_rows)
    return BlockShardCsr(blocks=blocks, cols=cols, mask=mask,
                         src_rows=src_rows, out_rows=vb * block)


@dataclasses.dataclass
class PartitionedGraph:
    """Static-shape per-partition buffers for shard_map execution."""
    n: int                      # number of partitions (mesh size)
    slots: int                  # P: padded vertices per partition
    edges_per_part: int         # E: padded edges per partition
    boundary_slots: int         # B: padded boundary rows per partition
    feats: np.ndarray           # [n, P, F] local features (padded rows = 0)
    vertex_mask: np.ndarray     # [n, P] 1 for real vertices
    # Edge connectivity, partitioned by the *receiver*'s owner:
    senders_global: np.ndarray  # [n, E] index into flattened [n*P] table
    senders_halo: np.ndarray    # [n, E] index into flattened [n*B] boundary table
    receivers_local: np.ndarray # [n, E] 0..P-1
    edge_mask: np.ndarray       # [n, E]
    # Boundary packing: rows each partition contributes to the halo table.
    boundary_rows: np.ndarray   # [n, B] local slot ids (padded w/ 0)
    boundary_mask: np.ndarray   # [n, B]
    # Self-edges for GAT (senders point at own row in the gathered table).
    self_senders_global: np.ndarray  # [n, P]
    self_senders_halo: np.ndarray    # [n, P]
    # Inverse permutation: result row for global vertex v lives at
    # (part[v], slot[v]).
    part_of: np.ndarray         # [V]
    slot_of: np.ndarray         # [V]
    # Pre-blocked shard-local adjacency for the Pallas aggregation path:
    # sum-aggregate = local_csr @ h_local + halo_csr @ gathered_halo.
    # None when build_partitioned ran with build_blocks=False.
    local_csr: Optional[BlockShardCsr] = None
    halo_csr: Optional[BlockShardCsr] = None

    def unpermute(self, out: np.ndarray) -> np.ndarray:
        """[n, P, D] stacked partition outputs -> [V, D] original order."""
        return out[self.part_of, self.slot_of]

    def unpermute_stack(self, out: np.ndarray) -> np.ndarray:
        """[n, B, P, D] batched partition outputs -> [B, V, D]."""
        return np.moveaxis(out[self.part_of, :, self.slot_of], 0, 1)

    def feature_stack(self, features: np.ndarray) -> np.ndarray:
        """[B, V, F] micro-batch -> [n, B, P, F] per-partition tables.

        The batched counterpart of ``with_features``: every example is
        scattered into the same padded slot layout (padded rows zero), so
        one shard_map launch serves the whole batch.
        """
        features = np.asarray(features, np.float32)
        b, v, f = features.shape
        feats = np.zeros((self.n, b, self.slots, f), np.float32)
        feats[self.part_of, :, self.slot_of] = np.moveaxis(features, 0, 1)
        return feats

    def shard_inputs(self, feats: np.ndarray, kernels: bool) -> list:
        """The host arrays a shard_map program takes after the params, in
        operand order: ``feats`` (the [n, P, F] or [n, B, P, F] table),
        the partition layout and, on the kernel path, both block-CSR
        shards."""
        arrays = [feats, self.vertex_mask, self.senders_global,
                  self.senders_halo, self.receivers_local, self.edge_mask,
                  self.boundary_rows, self.boundary_mask,
                  self.self_senders_global, self.self_senders_halo]
        if kernels:
            for csr in (self.local_csr, self.halo_csr):
                arrays += [csr.blocks, csr.cols, csr.mask]
        return arrays

    def with_features(self, features: np.ndarray) -> "PartitionedGraph":
        """Same layout (and block-CSR shards), fresh per-vertex features.

        Serving calls this once per query — the partition structure is
        feature-independent, so only the [n, P, F] table is rebuilt.
        """
        features = np.asarray(features, np.float32)
        feats = np.zeros((self.n, self.slots, features.shape[1]), np.float32)
        feats[self.part_of, self.slot_of] = features
        return dataclasses.replace(self, feats=feats)


def build_partitioned(g: Graph, assignment: np.ndarray,
                      pad_multiple: int = 8,
                      build_blocks: bool = True,
                      n: Optional[int] = None,
                      prev: Optional["PartitionedGraph"] = None,
                      dirty_local: Optional[np.ndarray] = None,
                      dirty_halo: Optional[np.ndarray] = None
                      ) -> PartitionedGraph:
    """Lay the graph out per-partition with static padded shapes.

    Padding conventions: every partition shares one slot count P (max
    partition size rounded up to ``pad_multiple``), one edge capacity E
    and one boundary capacity B; padded rows/edges carry zeroed features
    and 0.0 masks. Empty partitions (``assignment`` skipping a part id)
    and single-vertex shards are legal — they simply pad everywhere.
    ``n`` pins the partition count (needed when trailing partitions may be
    empty, e.g. after a graph update empties a shard).

    ``build_blocks=True`` additionally pre-blocks each shard's adjacency
    into the two ELL-block-CSR operands of the Pallas aggregation path
    (``local_csr`` over the P local slots, ``halo_csr`` over the [n*B]
    gathered halo table); pass False to skip that host-side work when only
    the segment-sum path will run.

    Dirty-shard rebuild: ``prev`` (a layout for the *previous* revision of
    the graph) plus ``dirty_local`` / ``dirty_halo`` (partition ids whose
    operands a graph delta invalidated — see
    ``core.incremental.dirty_partitions``) reuse every clean shard's
    pre-blocked operands instead of re-blocking them.  The cheap padded COO
    buffers are always recomputed, so the result is bit-identical to a
    from-scratch build; reuse silently degrades to a full re-block when the
    padded layout is incompatible (slot/boundary capacity changed).
    """
    assignment = np.asarray(assignment, np.int64)
    n = (int(assignment.max()) + 1) if n is None else int(n)
    parts: List[np.ndarray] = [np.flatnonzero(assignment == p) for p in range(n)]
    sizes = np.array([len(p) for p in parts])
    slots = int(-(-sizes.max() // pad_multiple) * pad_multiple)

    part_of = assignment
    slot_of = np.zeros(g.num_vertices, np.int64)
    for p, vs in enumerate(parts):
        slot_of[vs] = np.arange(len(vs))

    f = g.feature_dim
    feats = np.zeros((n, slots, f), np.float32)
    vmask = np.zeros((n, slots), np.float32)
    for p, vs in enumerate(parts):
        feats[p, :len(vs)] = g.features[vs]
        vmask[p, :len(vs)] = 1.0

    # Edges grouped by receiver's partition.
    recv_part = part_of[g.receivers]
    edge_lists = [np.flatnonzero(recv_part == p) for p in range(n)]
    e_max = max(1, max(len(e) for e in edge_lists))
    e_pad = int(-(-e_max // pad_multiple) * pad_multiple)

    # Boundary rows: vertices read by any foreign partition.
    boundary_ids = []
    for p in range(n):
        cross = (part_of[g.senders] == p) & (recv_part != p)
        boundary_ids.append(np.unique(g.senders[cross]))
    b_max = max(1, max(len(b) for b in boundary_ids))
    b_pad = int(-(-b_max // pad_multiple) * pad_multiple)

    # halo index of vertex v (valid only if v is in its owner's boundary set)
    halo_slot = np.zeros(g.num_vertices, np.int64)
    for p, bs in enumerate(boundary_ids):
        halo_slot[bs] = np.arange(len(bs))

    senders_global = np.zeros((n, e_pad), np.int32)
    senders_halo = np.zeros((n, e_pad), np.int32)
    receivers_local = np.zeros((n, e_pad), np.int32)
    edge_mask = np.zeros((n, e_pad), np.float32)
    boundary_rows = np.zeros((n, b_pad), np.int32)
    boundary_mask = np.zeros((n, b_pad), np.float32)
    local_edges, halo_edges = [], []
    for p in range(n):
        eids = edge_lists[p]
        s, r = g.senders[eids], g.receivers[eids]
        k = len(eids)
        senders_global[p, :k] = part_of[s] * slots + slot_of[s]
        # local senders also appear in the halo table? no — local senders are
        # read from the local shard directly in halo mode: point them at the
        # *own* boundary copy when they are boundary rows, else we route local
        # edges through the local table. To keep a single gather, halo mode
        # uses a combined table [local P rows | n*B halo rows]; local senders
        # use their local slot, remote senders use P + their halo position.
        local = part_of[s] == p
        senders_halo[p, :k] = np.where(
            local, slot_of[s],
            slots + part_of[s] * b_pad + halo_slot[s]).astype(np.int32)
        receivers_local[p, :k] = slot_of[r]
        edge_mask[p, :k] = 1.0
        bs = boundary_ids[p]
        boundary_rows[p, :len(bs)] = slot_of[bs]
        boundary_mask[p, :len(bs)] = 1.0
        # Unpadded per-shard edge splits for the block-CSR (kernel) path:
        # local senders read the shard's own rows, remote senders read the
        # gathered [n*B] halo table.
        local_edges.append((slot_of[s[local]], slot_of[r[local]]))
        halo_edges.append((part_of[s[~local]] * b_pad + halo_slot[s[~local]],
                           slot_of[r[~local]]))

    self_g = np.zeros((n, slots), np.int32)
    self_h = np.zeros((n, slots), np.int32)
    for p in range(n):
        self_g[p] = p * slots + np.arange(slots)
        self_h[p] = np.arange(slots)  # local rows in combined halo table

    local_csr = halo_csr = None
    if build_blocks:
        # Clean masks for shard reuse: with no prev layout (or no dirty
        # information) everything is rebuilt; shard-level compatibility
        # guards live in _stack_block_shards.
        prev_l = prev_h = clean_l = clean_h = None
        if (prev is not None and prev.n == n and prev.slots == slots
                and dirty_local is not None and dirty_halo is not None):
            if prev.local_csr is not None:
                prev_l = prev.local_csr
                clean_l = np.ones(n, bool)
                clean_l[np.asarray(dirty_local, np.int64)] = False
            if prev.halo_csr is not None and prev.boundary_slots == b_pad:
                prev_h = prev.halo_csr
                clean_h = np.ones(n, bool)
                clean_h[np.asarray(dirty_halo, np.int64)] = False
        local_csr = _stack_block_shards(local_edges, slots, slots,
                                        prev=prev_l, clean=clean_l)
        halo_csr = _stack_block_shards(halo_edges, slots, n * b_pad,
                                       prev=prev_h, clean=clean_h)

    return PartitionedGraph(
        n=n, slots=slots, edges_per_part=e_pad, boundary_slots=b_pad,
        feats=feats, vertex_mask=vmask,
        senders_global=senders_global, senders_halo=senders_halo,
        receivers_local=receivers_local, edge_mask=edge_mask,
        boundary_rows=boundary_rows, boundary_mask=boundary_mask,
        self_senders_global=self_g, self_senders_halo=self_h,
        part_of=part_of, slot_of=slot_of,
        local_csr=local_csr, halo_csr=halo_csr)


def _layer_edges(slots: int, senders, kind: str, self_senders,
                 receivers, emask, vmask):
    """EdgeList for one partition; GAT gets explicit self-edges."""
    if kind == "gat":
        s = jnp.concatenate([senders, self_senders])
        r = jnp.concatenate([receivers,
                             jnp.arange(slots, dtype=receivers.dtype)])
        m = jnp.concatenate([emask, vmask])
        return EdgeList(s, r, m, slots)
    return EdgeList(senders, receivers, emask, slots)


def _wire_quantize(h: jnp.ndarray, levels: float = 255.0):
    """Per-row linear quantization of the halo wire payload (jit-safe).

    Mirrors ``compression._quantize_rows`` at 8 bits: uint8 codes plus one
    f32 (scale, min) pair per row. All-zero (masked padding) rows get
    code 0 / scale ~0 / min 0 and dequantize to exactly 0. ``h`` may carry
    leading batch axes (rows are the second-to-last axis): the reduction
    runs over the feature (last) axis either way, so batched quantization
    is bit-identical per row to the single-query call.
    """
    mins = h.min(axis=-1)
    scales = jnp.maximum(h.max(axis=-1) - mins, 1e-12) / levels
    codes = jnp.clip(jnp.round((h - mins[..., None]) / scales[..., None]),
                     0, levels).astype(jnp.uint8)
    return codes, scales, mins


def _kernel_pad(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Zero-pad a source table to the kernel grid: ``rows`` source rows
    (multiple of BLOCK) and a feature count the f-tiling accepts. ``x``
    may be a [V, F] table or a stacked [B, V, F] micro-batch."""
    v, f = x.shape[-2:]
    pad = ((0, rows - v), (0, padded_feature_dim(f) - f))
    if x.ndim == 3:
        return jnp.pad(x, ((0, 0),) + pad)
    return jnp.pad(x, pad)


def _gathered_stack(x: jnp.ndarray) -> jnp.ndarray:
    """[n, B, R, F...] all_gather output -> [B, n*R, F...] per-example
    tables (pure data movement; rows land in the same order the serial
    path's ``.reshape(-1, f)`` produces)."""
    n, b = x.shape[:2]
    return jnp.moveaxis(x, 0, 1).reshape((b, n * x.shape[2]) + x.shape[3:])


#: Compiled shard_map programs, keyed by everything a program bakes in
#: statically (model kind, exchange, aggregation path, mesh devices, the
#: PartitionedGraph's static slot/row geometry). The model params and
#: every per-partition buffer are traced *operands*, so one cached
#: program serves every query — and every micro-batch size, since jit
#: re-specializes on operand shapes under the same wrapper — instead of
#: re-tracing and re-compiling the whole BSP program per call.
_PROGRAM_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_PROGRAM_CACHE_MAX = 32


def _cached_program(key: tuple, build):
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        fn = build()
        _PROGRAM_CACHE[key] = fn
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.popitem(last=False)
    else:
        _PROGRAM_CACHE.move_to_end(key)
    return fn


#: Field names of the _program_key tuple, in order. The cache audit
#: (repro.analysis.cache_audit) checks every live key against this and
#: maps each EngineConfig knob onto the field that carries it — keep the
#: three in sync when adding a knob that changes lowering.
PROGRAM_KEY_FIELDS = ("tag", "kind", "axis", "exchange", "use_kernels",
                      "halo_quant", "interpret", "geometry", "mesh_key")


def _program_key(tag: str, kind: str, pg: PartitionedGraph, mesh: Mesh,
                 axis: str, exchange: str, use_kernels: bool,
                 halo_quant: bool, interpret: bool) -> tuple:
    """Everything the shard program closes over statically."""
    geometry = (pg.n, pg.slots, pg.boundary_slots,
                None if pg.local_csr is None else pg.local_csr.src_rows,
                None if pg.halo_csr is None else pg.halo_csr.src_rows)
    mesh_key = (tuple(d.id for d in mesh.devices.flat),
                tuple(mesh.axis_names))
    return (tag, kind, axis, exchange, use_kernels, halo_quant, interpret,
            geometry, mesh_key)


def bsp_apply(params, kind: str, pg: PartitionedGraph, mesh: Mesh,
              axis: str = "fog", exchange: str = "halo",
              aggregation: str = "segment_sum",
              halo_quant: bool = False) -> jnp.ndarray:
    """Distributed K-layer GNN inference; returns [n, P, D] device outputs.

    ``aggregation`` selects the shard-local aggregation path (see module
    docstring); ``halo_quant=True`` (kernel path only) quantizes the halo
    rows to uint8 *before* the all_gather and dequantizes them inside the
    fused ``dequant_spmm`` kernel — the wire carries 1 byte/feature plus
    8 bytes/row instead of 4 bytes/feature.
    """
    _, layer_fn = LAYER_FNS[kind]
    mode = resolve_aggregation(aggregation, kind, exchange=exchange)
    exchange = _wire_exchange(exchange)
    use_kernels = mode == "pallas"
    if use_kernels and (pg.local_csr is None or pg.halo_csr is None):
        raise ValueError(
            "aggregation='pallas' needs the block-CSR shards; rebuild the "
            "PartitionedGraph with build_partitioned(..., build_blocks=True)")
    if halo_quant and not use_kernels:
        raise ValueError("halo_quant requires the 'pallas' aggregation path")
    interpret = jax.default_backend() != "tpu"
    # Bind the layout statics to locals: shard_fn must NOT close over the
    # PartitionedGraph itself, or the cached program (_PROGRAM_CACHE)
    # would pin retired graphs' feature/tile buffers until LRU eviction.
    slots = pg.slots
    local_rows = None if pg.local_csr is None else pg.local_csr.src_rows
    halo_rows = None if pg.halo_csr is None else pg.halo_csr.src_rows

    def shard_fn(params, feats, vmask, s_g, s_h, recv, emask, brows, bmask,
                 self_g, self_h, *kops):
        nlayers = len(params)
        # shard_map blocks: feats [1, P, F] etc. — squeeze the leading axis.
        h = feats[0]
        vm, sg, sh = vmask[0], s_g[0], s_h[0]
        rc, em = recv[0], emask[0]
        br, bm = brows[0], bmask[0]
        selg, selh = self_g[0], self_h[0]
        if use_kernels:
            lblk, lcol, lmsk, hblk, hcol, hmsk = (a[0] for a in kops)
        for li, p in enumerate(params):
            act_last = li == nlayers - 1
            kwargs = {}
            if exchange == "allgather":
                h_all = jax.lax.all_gather(h, axis)          # [n, P, F]
                h_src = h_all.reshape(-1, h.shape[-1])
                edges = _layer_edges(slots, sg, kind, selg, rc, em, vm)
            elif exchange == "halo":
                hb = h[br] * bm[:, None]                      # [B, F]
                edges = _layer_edges(slots, sh, kind, selh, rc, em, vm)
                if use_kernels:
                    # Kernel path: keep local and halo operands separate —
                    # sum-aggregate = local SpMM + halo SpMM — instead of
                    # concatenating one combined gather table.
                    f = h.shape[-1]
                    h_src = None
                    if halo_quant:
                        codes, sc, mn = _wire_quantize(hb)
                        codes = jax.lax.all_gather(
                            codes, axis).reshape(-1, f)
                        # One collective for both row parameters.
                        sm = jax.lax.all_gather(
                            jnp.stack([sc, mn], axis=-1), axis).reshape(-1, 2)
                        rows = halo_rows
                        codes = _kernel_pad(codes, rows)
                        sm = jnp.pad(sm, ((0, rows - sm.shape[0]), (0, 0)))
                        sc, mn = sm[:, 0], sm[:, 1]

                        def halo_agg(_f=f):
                            return dequant_spmm(
                                hblk, hcol, hmsk, codes, sc, mn,
                                interpret=interpret)[:slots, :_f]
                    else:
                        halo = jax.lax.all_gather(
                            hb, axis).reshape(-1, h.shape[-1])
                        halo = _kernel_pad(halo, halo_rows)

                        def halo_agg(_f=f):
                            return block_spmm(
                                hblk, hcol, hmsk, halo,
                                interpret=interpret)[:slots, :_f]

                    def kernel_sum(h_loc, edges_, h_src_=None, _f=f,
                                   _halo_agg=halo_agg):
                        loc = _kernel_pad(h_loc, local_rows)
                        out = block_spmm(lblk, lcol, lmsk, loc,
                                         interpret=interpret)
                        return out[:slots, :_f] + _halo_agg()

                    if kind == "sage":   # SAGE aggregates the mean
                        def kernel_agg(h_loc, edges_, h_src_=None,
                                       _sum=kernel_sum):
                            deg = masked_degree(edges_)
                            return (_sum(h_loc, edges_, h_src_)
                                    / jnp.maximum(deg, 1.0)[:, None])
                    else:
                        kernel_agg = kernel_sum
                    kwargs["aggregate"] = kernel_agg
                else:
                    halo = jax.lax.all_gather(hb, axis)       # [n, B, F]
                    h_src = jnp.concatenate(
                        [h, halo.reshape(-1, h.shape[-1])], axis=0)
            else:
                raise ValueError(exchange)
            if act_last:
                h = layer_fn(p, h, edges, activation=None, h_src=h_src,
                             **kwargs)
            else:
                h = layer_fn(p, h, edges, h_src=h_src, **kwargs)
            h = h * vm[:, None]  # keep padded rows at zero
        return h[None]

    spec = P(axis, None, None)
    host = pg.shard_inputs(pg.feats, use_kernels)
    # P() as a pytree-prefix spec: the model params ride along as a fully
    # replicated *operand* (not a closure constant), so the compiled
    # program below is reusable across queries and plans.
    in_specs = [P()] + [P(axis, *([None] * (a.ndim - 1))) for a in host]
    operands = [jnp.asarray(a) for a in host]
    # check_vma is off on the kernel path: pallas_call has no shard_map
    # replication rule, and every operand and output here is explicitly
    # partitioned, so the check adds nothing.
    fn = _cached_program(
        _program_key("apply", kind, pg, mesh, axis, exchange, use_kernels,
                     halo_quant, interpret),
        lambda: jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                      in_specs=tuple(in_specs),
                                      out_specs=spec,
                                      check_vma=not use_kernels)))
    return fn(list(params), *operands)


def bsp_apply_many(params, kind: str, pg: PartitionedGraph,
                   feat_stack: np.ndarray, mesh: Mesh, axis: str = "fog",
                   exchange: str = "halo", aggregation: str = "segment_sum",
                   halo_quant: bool = False) -> jnp.ndarray:
    """Distributed inference over a whole micro-batch in ONE traced call.

    ``feat_stack`` is the [n, B, P, F] table from
    ``PartitionedGraph.feature_stack``; returns [n, B, P, D]. The batch
    rides every stage of the per-layer BSP step:

      * collectives ship the stacked boundary rows — one all_gather per
        layer for the whole batch instead of B (the wire payload is B x
        bigger per sync, but the K*delta sync count stays that of a single
        query);
      * the kernel path aggregates with the batch-axis grid kernels
        (``block_spmm_batched`` / ``dequant_spmm_batched``): one fused
        dispatch per (layer, local/halo operand) with the block-CSR
        operands and scalar-prefetched column table shared across the
        batch, and the GCN/SAGE layer update broadcasting over the leading
        axis;
      * the segment-sum path (and GAT's per-layer attention re-weighting)
        runs the per-example layer under ``jax.vmap`` — the vmapped edge-
        weighted path — which XLA batches into one program.

    Every per-example result is bit-identical to the serial ``bsp_apply``
    (asserted by tests/test_batched_exec.py): vmap, broadcast dense
    algebra and the batched kernels all preserve the per-example op
    sequence.
    """
    _, layer_fn = LAYER_FNS[kind]
    mode = resolve_aggregation(aggregation, kind, exchange=exchange)
    exchange = _wire_exchange(exchange)
    use_kernels = mode == "pallas"
    if use_kernels and (pg.local_csr is None or pg.halo_csr is None):
        raise ValueError(
            "aggregation='pallas' needs the block-CSR shards; rebuild the "
            "PartitionedGraph with build_partitioned(..., build_blocks=True)")
    if halo_quant and not use_kernels:
        raise ValueError("halo_quant requires the 'pallas' aggregation path")
    interpret = jax.default_backend() != "tpu"
    # Bind the layout statics to locals: shard_fn must NOT close over the
    # PartitionedGraph itself, or the cached program (_PROGRAM_CACHE)
    # would pin retired graphs' feature/tile buffers until LRU eviction.
    slots = pg.slots
    local_rows = None if pg.local_csr is None else pg.local_csr.src_rows
    halo_rows = None if pg.halo_csr is None else pg.halo_csr.src_rows

    def shard_fn(params, feats, vmask, s_g, s_h, recv, emask, brows, bmask,
                 self_g, self_h, *kops):
        nlayers = len(params)
        h = feats[0]                                   # [B, P, F]
        vm, sg, sh = vmask[0], s_g[0], s_h[0]
        rc, em = recv[0], emask[0]
        br, bm = brows[0], bmask[0]
        selg, selh = self_g[0], self_h[0]
        if use_kernels:
            lblk, lcol, lmsk, hblk, hcol, hmsk = (a[0] for a in kops)
        for li, p in enumerate(params):
            act_last = li == nlayers - 1
            kwargs = {}
            if exchange == "allgather":
                h_all = jax.lax.all_gather(h, axis)    # [n, B, P, F]
                h_src = _gathered_stack(h_all)          # [B, n*P, F]
                edges = _layer_edges(slots, sg, kind, selg, rc, em, vm)
            elif exchange == "halo":
                hb = h[:, br] * bm[:, None]             # [B, Bnd, F]
                edges = _layer_edges(slots, sh, kind, selh, rc, em, vm)
                if use_kernels:
                    f = h.shape[-1]
                    h_src = None
                    if halo_quant:
                        codes, sc, mn = _wire_quantize(hb)
                        codes = _gathered_stack(
                            jax.lax.all_gather(codes, axis))   # [B, nB, F]
                        sm = _gathered_stack(jax.lax.all_gather(
                            jnp.stack([sc, mn], axis=-1), axis))  # [B,nB,2]
                        rows = halo_rows
                        codes = _kernel_pad(codes, rows)
                        sm = jnp.pad(
                            sm, ((0, 0), (0, rows - sm.shape[1]), (0, 0)))
                        sc, mn = sm[..., 0], sm[..., 1]

                        def halo_agg(_f=f):
                            return dequant_spmm_batched(
                                hblk, hcol, hmsk, codes, sc, mn,
                                interpret=interpret)[:, :slots, :_f]
                    else:
                        halo = _gathered_stack(
                            jax.lax.all_gather(hb, axis))
                        halo = _kernel_pad(halo, halo_rows)

                        def halo_agg(_f=f):
                            return block_spmm_batched(
                                hblk, hcol, hmsk, halo,
                                interpret=interpret)[:, :slots, :_f]

                    def kernel_sum(h_loc, _f=f, _halo_agg=halo_agg):
                        loc = _kernel_pad(h_loc, local_rows)
                        out = block_spmm_batched(lblk, lcol, lmsk, loc,
                                                 interpret=interpret)
                        return out[:, :slots, :_f] + _halo_agg()
                else:
                    halo = jax.lax.all_gather(hb, axis)   # [n, B, Bnd, F]
                    h_src = jnp.concatenate(
                        [h, _gathered_stack(halo)], axis=1)
            else:
                raise ValueError(exchange)
            if act_last:
                kwargs["activation"] = None
            if use_kernels:
                # Grid-axis kernel path: ONE fused batched SpMM dispatch
                # computes every example's neighbor sum, then the shared
                # dense tail (vmapped per example — see
                # layers.apply_layer_with_sum for the bit-identity
                # rationale).
                h = apply_layer_with_sum(kind, p, h, edges, kernel_sum(h),
                                         last=act_last)
            else:
                # Vmapped edge-weighted path: gathers/segment ops (and
                # GAT's attention softmax) index vertex rows, so the
                # per-example layer runs under vmap.
                h = jax.vmap(lambda hh, ss, _p=p, _kw=kwargs: layer_fn(
                    _p, hh, edges, h_src=ss, **_kw))(h, h_src)
            h = h * vm[:, None]  # [B, P, F] * [P, 1]: padded rows stay 0
        return h[None]

    spec = P(axis, None, None, None)
    host = pg.shard_inputs(feat_stack, use_kernels)
    # Params ride as a replicated operand (P() pytree-prefix spec) so the
    # compiled program is reusable — see _PROGRAM_CACHE.
    in_specs = [P()] + [P(axis, *([None] * (a.ndim - 1))) for a in host]
    operands = [jnp.asarray(a) for a in host]
    fn = _cached_program(
        _program_key("apply_many", kind, pg, mesh, axis, exchange,
                     use_kernels, halo_quant, interpret),
        lambda: jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                      in_specs=tuple(in_specs),
                                      out_specs=spec,
                                      check_vma=not use_kernels)))
    return fn(list(params), *operands)


def _bsp_apply_layers(params, kind: str, pg: PartitionedGraph, feats_op,
                      mesh: Mesh, axis: str = "fog", exchange: str = "halo",
                      aggregation: str = "segment_sum",
                      halo_quant: bool = False, many: bool = False,
                      dirty=None, cached=None):
    """Capture / frontier variants of ``bsp_apply`` / ``bsp_apply_many``.

    Runs the same per-layer BSP step as the plain programs but returns a
    tuple of EVERY layer's [n, (B,) P, F_l] activations (the last entry is
    the plain program's output, bit for bit — same op sequence modulo dead
    code).  With ``dirty`` / ``cached`` it becomes the frontier-restricted
    shard apply: ``dirty`` is a [n, K, P] per-layer dirty-row mask,
    ``cached`` a list of K [n, P, F_l] activation tables from the last
    full pass, and each layer

      * segment path: masks edges to dirty receivers (``em * dirty[rc]``)
        — a dirty row keeps its FULL incoming edge subsequence, so its
        segment sums and masked degree accumulate in the full pass's
        order;
      * kernel path: zeroes the tile masks of clean 128-row blocks so the
        Pallas SpMM only accumulates dirty row-blocks (the edge mask
        stays full: degrees must be exact), and merges at row-block
        granularity — every row of a live block sees its full tile set,
        so its value equals the full pass's;

    then scatter-merges recomputed rows into the cached table with
    ``jnp.where`` (an elementwise select: clean rows keep the cached
    bits, including -0.0 signs, which an arithmetic blend would flip).
    The next layer's halo exchange reads the MERGED table, so the result
    is bit-identical to a from-scratch pass by induction — provided the
    dirty mask is a sound k-hop closure (``core.frontier``) and the
    cached tables came from this graph revision (the Session's
    ``ActivationCache`` tags enforce both).
    """
    _, layer_fn = LAYER_FNS[kind]
    mode = resolve_aggregation(aggregation, kind, exchange=exchange)
    exchange = _wire_exchange(exchange)
    use_kernels = mode == "pallas"
    frontier = dirty is not None
    if use_kernels and (pg.local_csr is None or pg.halo_csr is None):
        raise ValueError(
            "aggregation='pallas' needs the block-CSR shards; rebuild the "
            "PartitionedGraph with build_partitioned(..., build_blocks=True)")
    if halo_quant and not use_kernels:
        raise ValueError("halo_quant requires the 'pallas' aggregation path")
    if frontier and kind not in KERNEL_KINDS:
        raise ValueError(
            f"frontier execution supports kinds {KERNEL_KINDS} (static-sum "
            f"aggregation); {kind!r} re-weights edges per layer")
    interpret = jax.default_backend() != "tpu"
    # Bind layout statics to locals (never close over pg — see bsp_apply).
    slots = pg.slots
    local_rows = None if pg.local_csr is None else pg.local_csr.src_rows
    halo_rows = None if pg.halo_csr is None else pg.halo_csr.src_rows
    out_rows = None if pg.local_csr is None else pg.local_csr.out_rows

    def shard_fn(params, *ops):
        feats, vmask, s_g, s_h, recv, emask = ops[:6]
        brows, bmask, self_g, self_h = ops[6:10]
        rest = ops[10:]
        dm = cch = None
        if frontier:
            dm = rest[0][0]                    # [K, P]
            cch = [c[0] for c in rest[1]]      # K tables [P, F_l]
            rest = rest[2:]
        if use_kernels:
            lblk, lcol, lmsk, hblk, hcol, hmsk = (a[0] for a in rest)
        nlayers = len(params)
        h = feats[0]                           # [P, F] or [B, P, F]
        vm, sg, sh = vmask[0], s_g[0], s_h[0]
        rc, em = recv[0], emask[0]
        br, bm = brows[0], bmask[0]
        selg, selh = self_g[0], self_h[0]
        outs = []
        for li, p in enumerate(params):
            act_last = li == nlayers - 1
            kwargs = {}
            em_l = em
            lmsk_l = hmsk_l = merge_row = None
            if use_kernels:
                lmsk_l, hmsk_l = lmsk, hmsk
            if frontier:
                drow = dm[li]                  # [P]
                if use_kernels:
                    dblk = jnp.pad(drow, (0, out_rows - slots)) \
                        .reshape(-1, BLOCK).max(axis=1)
                    lmsk_l = lmsk * dblk[:, None]
                    hmsk_l = hmsk * dblk[:, None]
                    merge_row = jnp.repeat(dblk, BLOCK)[:slots]
                else:
                    em_l = em * drow[rc]
                    merge_row = drow
            if exchange == "allgather":
                h_all = jax.lax.all_gather(h, axis)
                h_src = (_gathered_stack(h_all) if many
                         else h_all.reshape(-1, h.shape[-1]))
                edges = _layer_edges(slots, sg, kind, selg, rc, em_l, vm)
            elif exchange == "halo":
                hb = (h[:, br] if many else h[br]) * bm[:, None]
                edges = _layer_edges(slots, sh, kind, selh, rc, em_l, vm)
                if use_kernels:
                    f = h.shape[-1]
                    h_src = None
                    if halo_quant:
                        codes, sc, mn = _wire_quantize(hb)
                        if many:
                            codes = _gathered_stack(
                                jax.lax.all_gather(codes, axis))
                            sm = _gathered_stack(jax.lax.all_gather(
                                jnp.stack([sc, mn], axis=-1), axis))
                            codes = _kernel_pad(codes, halo_rows)
                            sm = jnp.pad(sm, ((0, 0),
                                              (0, halo_rows - sm.shape[1]),
                                              (0, 0)))
                            sc, mn = sm[..., 0], sm[..., 1]

                            def halo_agg(_f=f, _m=hmsk_l, _c=codes,
                                         _s=sc, _n=mn):
                                return dequant_spmm_batched(
                                    hblk, hcol, _m, _c, _s, _n,
                                    interpret=interpret)[:, :slots, :_f]
                        else:
                            codes = jax.lax.all_gather(
                                codes, axis).reshape(-1, f)
                            sm = jax.lax.all_gather(
                                jnp.stack([sc, mn], axis=-1),
                                axis).reshape(-1, 2)
                            codes = _kernel_pad(codes, halo_rows)
                            sm = jnp.pad(sm, ((0, halo_rows - sm.shape[0]),
                                              (0, 0)))
                            sc, mn = sm[:, 0], sm[:, 1]

                            def halo_agg(_f=f, _m=hmsk_l, _c=codes,
                                         _s=sc, _n=mn):
                                return dequant_spmm(
                                    hblk, hcol, _m, _c, _s, _n,
                                    interpret=interpret)[:slots, :_f]
                    else:
                        if many:
                            halo = _gathered_stack(
                                jax.lax.all_gather(hb, axis))
                            halo = _kernel_pad(halo, halo_rows)

                            def halo_agg(_f=f, _m=hmsk_l, _h=halo):
                                return block_spmm_batched(
                                    hblk, hcol, _m, _h,
                                    interpret=interpret)[:, :slots, :_f]
                        else:
                            halo = jax.lax.all_gather(
                                hb, axis).reshape(-1, h.shape[-1])
                            halo = _kernel_pad(halo, halo_rows)

                            def halo_agg(_f=f, _m=hmsk_l, _h=halo):
                                return block_spmm(
                                    hblk, hcol, _m, _h,
                                    interpret=interpret)[:slots, :_f]
                    if many:
                        def kernel_sum(h_loc, _f=f, _m=lmsk_l,
                                       _halo_agg=halo_agg):
                            loc = _kernel_pad(h_loc, local_rows)
                            out = block_spmm_batched(lblk, lcol, _m, loc,
                                                     interpret=interpret)
                            return out[:, :slots, :_f] + _halo_agg()
                    else:
                        def kernel_sum(h_loc, edges_, h_src_=None, _f=f,
                                       _m=lmsk_l, _halo_agg=halo_agg):
                            loc = _kernel_pad(h_loc, local_rows)
                            out = block_spmm(lblk, lcol, _m, loc,
                                             interpret=interpret)
                            return out[:slots, :_f] + _halo_agg()
                else:
                    halo = jax.lax.all_gather(hb, axis)
                    if many:
                        h_src = jnp.concatenate(
                            [h, _gathered_stack(halo)], axis=1)
                    else:
                        h_src = jnp.concatenate(
                            [h, halo.reshape(-1, h.shape[-1])], axis=0)
            else:
                raise ValueError(exchange)
            if use_kernels and not many:
                if kind == "sage":
                    def kernel_agg(h_loc, edges_, h_src_=None,
                                   _sum=kernel_sum):
                        deg = masked_degree(edges_)
                        return (_sum(h_loc, edges_, h_src_)
                                / jnp.maximum(deg, 1.0)[:, None])
                else:
                    kernel_agg = kernel_sum
                kwargs["aggregate"] = kernel_agg
            if many:
                if act_last:
                    kwargs["activation"] = None
                if use_kernels:
                    h_new = apply_layer_with_sum(kind, p, h, edges,
                                                 kernel_sum(h),
                                                 last=act_last)
                else:
                    h_new = jax.vmap(
                        lambda hh, ss, _p=p, _kw=kwargs: layer_fn(
                            _p, hh, edges, h_src=ss, **_kw))(h, h_src)
            elif act_last:
                h_new = layer_fn(p, h, edges, activation=None, h_src=h_src,
                                 **kwargs)
            else:
                h_new = layer_fn(p, h, edges, h_src=h_src, **kwargs)
            h_new = h_new * vm[:, None]
            if frontier:
                h = jnp.where(merge_row[:, None] > 0, h_new, cch[li])
            else:
                h = h_new
            outs.append(h[None])
        return tuple(outs)

    spec = P(axis, None, None, None) if many else P(axis, None, None)
    spec2 = P(axis, None)
    spec3 = P(axis, None, None)
    in_specs = [P(), spec, spec2, spec2, spec2, spec2, spec2, spec2, spec2,
                spec2, spec2]
    operands = [jnp.asarray(feats_op), jnp.asarray(pg.vertex_mask),
                jnp.asarray(pg.senders_global), jnp.asarray(pg.senders_halo),
                jnp.asarray(pg.receivers_local), jnp.asarray(pg.edge_mask),
                jnp.asarray(pg.boundary_rows), jnp.asarray(pg.boundary_mask),
                jnp.asarray(pg.self_senders_global),
                jnp.asarray(pg.self_senders_halo)]
    if frontier:
        # The dirty masks ride as ONE [n, K, P] operand; the cached tables
        # as a list operand under a pytree-prefix spec (variable K / F_l
        # re-specialize jit under the same cached shard_map wrapper).
        operands.append(jnp.asarray(dirty, jnp.float32))
        in_specs.append(spec3)
        operands.append([jnp.asarray(c, jnp.float32) for c in cached])
        in_specs.append(spec3)
    if use_kernels:
        for csr in (pg.local_csr, pg.halo_csr):
            for arr in (csr.blocks, csr.cols, csr.mask):
                operands.append(jnp.asarray(arr))
                in_specs.append(P(axis, *([None] * (arr.ndim - 1))))
    tag = ("frontier" if frontier else "capture") + ("_many" if many else "")
    fn = _cached_program(
        _program_key(tag, kind, pg, mesh, axis, exchange, use_kernels,
                     halo_quant, interpret),
        lambda: jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                      in_specs=tuple(in_specs),
                                      out_specs=spec,
                                      check_vma=not use_kernels)))
    return fn(list(params), *operands)


def wait(out: jax.Array) -> jax.Array:
    """Block on a dispatched result under its own span; the host copy
    after it would block anyway, so no synchronization is added."""
    with tracing.span("execute.wait"):
        return jax.block_until_ready(out)


def _default_mesh(pg: PartitionedGraph, axis: str) -> Mesh:
    devs = np.array(jax.devices()[:pg.n])
    if len(devs) != pg.n:
        raise ValueError(
            f"need {pg.n} devices for {pg.n} partitions, have "
            f"{len(jax.devices())} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={pg.n}")
    return Mesh(devs, (axis,))


def bsp_infer_capture(params, kind: str, g: Graph, assignment: np.ndarray,
                      mesh: Optional[Mesh] = None, exchange: str = "halo",
                      axis: str = "fog", aggregation: str = "segment_sum",
                      halo_quant: bool = False,
                      pg: Optional[PartitionedGraph] = None):
    """``bsp_infer`` returning every layer: K arrays [V, F_l] in original
    vertex order (the last is the plain ``bsp_infer`` output, bit for
    bit). Feeds the Session's activation cache."""
    if pg is None:
        mode = resolve_aggregation(aggregation, kind, exchange=exchange)
        pg = build_partitioned(g, assignment, build_blocks=mode == "pallas")
    else:
        pg = pg.with_features(g.features)
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    outs = _bsp_apply_layers(params, kind, pg, pg.feats, mesh, axis,
                             exchange, aggregation, halo_quant, many=False)
    return [pg.unpermute(np.asarray(o)) for o in outs]


def bsp_infer_capture_many(params, kind: str, feats: np.ndarray,
                           pg: PartitionedGraph,
                           mesh: Optional[Mesh] = None,
                           exchange: str = "halo", axis: str = "fog",
                           aggregation: str = "segment_sum",
                           halo_quant: bool = False):
    """Batched capture: [B, V, F] micro-batch -> K arrays [B, V, F_l]."""
    stack = pg.feature_stack(np.asarray(feats, np.float32))
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    outs = _bsp_apply_layers(params, kind, pg, stack, mesh, axis, exchange,
                             aggregation, halo_quant, many=True)
    return [pg.unpermute_stack(np.asarray(o)) for o in outs]


def build_halo_tables(pg: PartitionedGraph, layer_inputs) -> List[np.ndarray]:
    """Pre-gathered per-layer halo tables for the stale-serve path.

    ``layer_inputs[l]`` is the [V, F_l] table of layer ``l``'s INPUT
    activations in original vertex order — layer 0's input is the raw
    feature matrix, layer ``l>0``'s input is layer ``l-1``'s output (e.g.
    from ``bsp_infer_capture``).  Returns K ``[n*B, F_l]`` tables laid out
    exactly like the synchronous exchange's
    ``all_gather(h[br] * bm[:, None]).reshape(-1, f)``: row ``p*B + i``
    carries partition ``p``'s i-th boundary row times its mask, padded
    rows zero.  Pure data movement through part_of/slot_of (no
    arithmetic), so replaying a table built from the same activations the
    fresh exchange shipped reproduces that exchange bit for bit.
    """
    tables = []
    brows = pg.boundary_rows.astype(np.int64)
    for act in layer_inputs:
        act = np.asarray(act, np.float32)
        f = act.shape[-1]
        shard = np.zeros((pg.n, pg.slots, f), np.float32)
        shard[pg.part_of, pg.slot_of] = act
        rows = np.take_along_axis(shard, brows[:, :, None], axis=1)
        rows = rows * pg.boundary_mask[:, :, None]
        tables.append(np.ascontiguousarray(
            rows.reshape(pg.n * pg.boundary_slots, f)))
    return tables


def _bsp_apply_stale(params, kind: str, pg: PartitionedGraph, feats_op,
                     halo_tables, mesh: Mesh, axis: str = "fog",
                     aggregation: str = "segment_sum", many: bool = False):
    """The ``halo_async`` stale serve: cross-partition reads come from the
    pre-gathered per-layer ``halo_tables`` (replicated operands) instead of
    a live per-layer collective, so no superstep stalls on the WAN.  Local
    rows always read the CURRENT features in ``feats_op``; only the halo
    rows are stale.  ``halo_quant`` does not apply — nothing crosses the
    wire.  Returns [n, (B,) P, D] device outputs like the plain programs.
    """
    _, layer_fn = LAYER_FNS[kind]
    mode = resolve_aggregation(aggregation, kind, exchange="halo_async")
    use_kernels = mode == "pallas"
    if use_kernels and (pg.local_csr is None or pg.halo_csr is None):
        raise ValueError(
            "aggregation='pallas' needs the block-CSR shards; rebuild the "
            "PartitionedGraph with build_partitioned(..., build_blocks=True)")
    if len(halo_tables) != len(params):
        raise ValueError(
            f"stale serve needs one halo table per layer: got "
            f"{len(halo_tables)} tables for {len(params)} layers")
    interpret = jax.default_backend() != "tpu"
    # Bind layout statics to locals (never close over pg — see bsp_apply).
    slots = pg.slots
    local_rows = None if pg.local_csr is None else pg.local_csr.src_rows
    halo_rows = None if pg.halo_csr is None else pg.halo_csr.src_rows

    def shard_fn(params, halos, feats, vmask, s_g, s_h, recv, emask, brows,
                 bmask, self_g, self_h, *kops):
        nlayers = len(params)
        h = feats[0]                               # [P, F] or [B, P, F]
        vm, sh = vmask[0], s_h[0]
        rc, em = recv[0], emask[0]
        selh = self_h[0]
        if use_kernels:
            lblk, lcol, lmsk, hblk, hcol, hmsk = (a[0] for a in kops)
        for li, p in enumerate(params):
            act_last = li == nlayers - 1
            kwargs = {}
            stale = halos[li]                      # [n*B, F_l] replicated
            edges = _layer_edges(slots, sh, kind, selh, rc, em, vm)
            if use_kernels:
                f = h.shape[-1]
                h_src = None
                halo = _kernel_pad(stale, halo_rows)
                if many:
                    halo = jnp.broadcast_to(halo, (h.shape[0],) + halo.shape)

                    def halo_agg(_f=f, _h=halo):
                        return block_spmm_batched(
                            hblk, hcol, hmsk, _h,
                            interpret=interpret)[:, :slots, :_f]

                    def kernel_sum(h_loc, _f=f, _halo_agg=halo_agg):
                        loc = _kernel_pad(h_loc, local_rows)
                        out = block_spmm_batched(lblk, lcol, lmsk, loc,
                                                 interpret=interpret)
                        return out[:, :slots, :_f] + _halo_agg()
                else:
                    def halo_agg(_f=f, _h=halo):
                        return block_spmm(hblk, hcol, hmsk, _h,
                                          interpret=interpret)[:slots, :_f]

                    def kernel_sum(h_loc, edges_, h_src_=None, _f=f,
                                   _halo_agg=halo_agg):
                        loc = _kernel_pad(h_loc, local_rows)
                        out = block_spmm(lblk, lcol, lmsk, loc,
                                         interpret=interpret)
                        return out[:slots, :_f] + _halo_agg()
            elif many:
                h_src = jnp.concatenate(
                    [h, jnp.broadcast_to(stale, (h.shape[0],) + stale.shape)],
                    axis=1)
            else:
                h_src = jnp.concatenate([h, stale], axis=0)
            if many:
                if act_last:
                    kwargs["activation"] = None
                if use_kernels:
                    h = apply_layer_with_sum(kind, p, h, edges,
                                             kernel_sum(h), last=act_last)
                else:
                    h = jax.vmap(lambda hh, ss, _p=p, _kw=kwargs: layer_fn(
                        _p, hh, edges, h_src=ss, **_kw))(h, h_src)
            else:
                if use_kernels:
                    if kind == "sage":
                        def kernel_agg(h_loc, edges_, h_src_=None,
                                       _sum=kernel_sum):
                            deg = masked_degree(edges_)
                            return (_sum(h_loc, edges_, h_src_)
                                    / jnp.maximum(deg, 1.0)[:, None])
                    else:
                        kernel_agg = kernel_sum
                    kwargs["aggregate"] = kernel_agg
                if act_last:
                    h = layer_fn(p, h, edges, activation=None, h_src=h_src,
                                 **kwargs)
                else:
                    h = layer_fn(p, h, edges, h_src=h_src, **kwargs)
            h = h * vm[:, None]
        return h[None]

    spec = P(axis, None, None, None) if many else P(axis, None, None)
    spec2 = P(axis, None)
    # Params AND the stale halo tables ride as replicated operands (P()
    # pytree-prefix specs) so the compiled program is reusable — see
    # _PROGRAM_CACHE.  The tables are graph state shared by every shard
    # and (in the batched program) every example.
    in_specs = [P(), P(), spec, spec2, spec2, spec2, spec2, spec2, spec2,
                spec2, spec2, spec2]
    operands = [jnp.asarray(feats_op), jnp.asarray(pg.vertex_mask),
                jnp.asarray(pg.senders_global), jnp.asarray(pg.senders_halo),
                jnp.asarray(pg.receivers_local), jnp.asarray(pg.edge_mask),
                jnp.asarray(pg.boundary_rows), jnp.asarray(pg.boundary_mask),
                jnp.asarray(pg.self_senders_global),
                jnp.asarray(pg.self_senders_halo)]
    if use_kernels:
        for csr in (pg.local_csr, pg.halo_csr):
            for arr in (csr.blocks, csr.cols, csr.mask):
                operands.append(jnp.asarray(arr))
                in_specs.append(P(axis, *([None] * (arr.ndim - 1))))
    tag = "stale_many" if many else "stale"
    fn = _cached_program(
        _program_key(tag, kind, pg, mesh, axis, "halo_async", use_kernels,
                     False, interpret),
        lambda: jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                      in_specs=tuple(in_specs),
                                      out_specs=spec,
                                      check_vma=not use_kernels)))
    tables = [jnp.asarray(t, jnp.float32) for t in halo_tables]
    return fn(list(params), tables, *operands)


def bsp_infer_stale(params, kind: str, feats: np.ndarray,
                    pg: PartitionedGraph, halo_tables,
                    mesh: Optional[Mesh] = None, axis: str = "fog",
                    aggregation: str = "segment_sum") -> np.ndarray:
    """Stale-halo distributed inference -> [V, D] in original vertex order.

    ``feats`` are the CURRENT [V, F] features (local reads stay fresh);
    ``halo_tables`` the recorded per-layer exchange payloads
    (``build_halo_tables``) a bounded-staleness serve may replay.
    """
    pg = pg.with_features(np.asarray(feats, np.float32))
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    out = np.asarray(_bsp_apply_stale(params, kind, pg, pg.feats,
                                      halo_tables, mesh, axis, aggregation))
    return pg.unpermute(out)


def bsp_infer_stale_many(params, kind: str, feats: np.ndarray,
                         pg: PartitionedGraph, halo_tables,
                         mesh: Optional[Mesh] = None, axis: str = "fog",
                         aggregation: str = "segment_sum") -> np.ndarray:
    """Batched stale-halo inference: [B, V, F] micro-batch -> [B, V, D];
    every example shares the same recorded halo tables (graph state, not
    per-request state)."""
    stack = pg.feature_stack(np.asarray(feats, np.float32))
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    out = np.asarray(_bsp_apply_stale(params, kind, pg, stack, halo_tables,
                                      mesh, axis, aggregation, many=True))
    return pg.unpermute_stack(out)


def _scatter_frontier(pg: PartitionedGraph, rows_per_layer, cached_layers):
    """Global frontier/cache state -> per-partition shard operands.

    Pure data movement through part_of/slot_of (no arithmetic), so the
    shard tables carry exactly the cached bits."""
    k = len(cached_layers)
    dm = np.zeros((pg.n, k, pg.slots), np.float32)
    for li, rows in enumerate(rows_per_layer):
        rows = np.asarray(rows, np.int64)
        dm[pg.part_of[rows], li, pg.slot_of[rows]] = 1.0
    ct = []
    for cl in cached_layers:
        cl = np.asarray(cl, np.float32)
        t = np.zeros((pg.n, pg.slots, cl.shape[-1]), np.float32)
        t[pg.part_of, pg.slot_of] = cl
        ct.append(t)
    return dm, ct


def bsp_infer_frontier(params, kind: str, feats: np.ndarray,
                       pg: PartitionedGraph, rows_per_layer, cached_layers,
                       mesh: Optional[Mesh] = None, exchange: str = "halo",
                       axis: str = "fog", aggregation: str = "segment_sum",
                       halo_quant: bool = False):
    """Frontier-restricted distributed inference.

    ``rows_per_layer[l]`` are the global vertex ids layer ``l`` must
    recompute (a sound closure from ``core.frontier``), ``cached_layers``
    the last full pass's K [V, F_l] tables for THIS graph revision.
    Returns the K merged tables in original vertex order; the last one is
    bit-identical to a full ``bsp_infer`` pass.
    """
    pg = pg.with_features(np.asarray(feats, np.float32))
    dm, ct = _scatter_frontier(pg, rows_per_layer, cached_layers)
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    outs = _bsp_apply_layers(params, kind, pg, pg.feats, mesh, axis,
                             exchange, aggregation, halo_quant, many=False,
                             dirty=dm, cached=ct)
    return [pg.unpermute(np.asarray(o)) for o in outs]


def bsp_infer_frontier_many(params, kind: str, feats: np.ndarray,
                            pg: PartitionedGraph, rows_per_layer,
                            cached_layers, mesh: Optional[Mesh] = None,
                            exchange: str = "halo", axis: str = "fog",
                            aggregation: str = "segment_sum",
                            halo_quant: bool = False):
    """Batched frontier pass over a stacked [B, V, F] micro-batch sharing
    one (unioned) dirty frontier; returns K merged [B, V, F_l] stacks."""
    stack = pg.feature_stack(np.asarray(feats, np.float32))
    dm, ct = _scatter_frontier(pg, rows_per_layer, cached_layers)
    if mesh is None:
        mesh = _default_mesh(pg, axis)
    outs = _bsp_apply_layers(params, kind, pg, stack, mesh, axis, exchange,
                             aggregation, halo_quant, many=True,
                             dirty=dm, cached=ct)
    return [pg.unpermute_stack(np.asarray(o)) for o in outs]


def bsp_infer(params, kind: str, g: Graph, assignment: np.ndarray,
              mesh: Optional[Mesh] = None, exchange: str = "halo",
              axis: str = "fog", aggregation: str = "segment_sum",
              halo_quant: bool = False,
              pg: Optional[PartitionedGraph] = None) -> np.ndarray:
    """End-to-end distributed inference -> [V, D] in original vertex order.

    With ``mesh=None`` a mesh over all available devices is built; the
    number of partitions must equal the mesh size. ``pg`` reuses prebuilt
    partition buffers (the features are refreshed from ``g``), which is
    what the serving path does per query.
    """
    with tracing.span("execute.dispatch") as span:
        kernels = resolve_aggregation(aggregation, kind,
                                      exchange=exchange) == "pallas"
        if pg is None:
            pg = build_partitioned(g, assignment, build_blocks=kernels)
        else:
            pg = pg.with_features(g.features)
        if mesh is None:
            mesh = _default_mesh(pg, axis)
        span.set_metadata(upload_bytes=sum(
            a.nbytes for a in pg.shard_inputs(pg.feats, kernels)))
        out = bsp_apply(params, kind, pg, mesh, axis, exchange,
                        aggregation=aggregation, halo_quant=halo_quant)
    out = wait(out)
    with tracing.span("execute.download", download_bytes=out.nbytes):
        return pg.unpermute(np.asarray(out))


def bsp_infer_many(params, kind: str, feats: np.ndarray,
                   pg: PartitionedGraph, mesh: Optional[Mesh] = None,
                   exchange: str = "halo", axis: str = "fog",
                   aggregation: str = "segment_sum",
                   halo_quant: bool = False) -> np.ndarray:
    """Batched end-to-end distributed inference -> [B, V, D].

    ``feats`` is a [B, V, F] stacked micro-batch; the prebuilt ``pg``
    supplies the layout (and block-CSR shards for the kernel path). One
    shard_map launch serves the whole batch — see ``bsp_apply_many``.
    """
    feats = np.asarray(feats, np.float32)
    if feats.ndim != 3:
        raise ValueError(f"bsp_infer_many takes a [B, V, F] stack, got "
                         f"shape {feats.shape}")
    with tracing.span("execute.dispatch") as span:
        stack = pg.feature_stack(feats)
        if mesh is None:
            mesh = _default_mesh(pg, axis)
        kernels = resolve_aggregation(aggregation, kind,
                                      exchange=exchange) == "pallas"
        span.set_metadata(upload_bytes=sum(
            a.nbytes for a in pg.shard_inputs(stack, kernels)))
        out = bsp_apply_many(params, kind, pg, stack, mesh, axis, exchange,
                             aggregation=aggregation, halo_quant=halo_quant)
    out = wait(out)
    with tracing.span("execute.download", download_bytes=out.nbytes):
        return pg.unpermute_stack(np.asarray(out))


def exchange_bytes(pg: PartitionedGraph, feature_dim: int,
                   exchange: str, dtype_bytes: int = 4,
                   row_overhead_bytes: int = 0) -> int:
    """Collective payload per BSP sync (for the communication roofline).

    ``dtype_bytes``/``row_overhead_bytes`` describe the wire format: the
    float32 exchange is (4, 0); the DAQ-fused kernel path ships uint8
    codes plus one f32 (scale, min) pair per row, i.e. (1, 8).
    """
    per_row = feature_dim * dtype_bytes + row_overhead_bytes
    if exchange == "allgather":
        return pg.n * pg.slots * per_row
    return pg.n * pg.boundary_slots * per_row


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
    """An EXCHANGES registry entry: one per-layer cross-fog exchange.

    ``stale_tolerant`` marks modes whose serves may replay recorded halo
    tables up to a staleness bound instead of running the collective
    (``EngineConfig.staleness_bound`` only applies to those entries).

    ``retryable`` + the retry knobs are the tier-1 fault-recovery hook:
    a transient loss of this exchange is retried with exponential
    backoff (``backoff_base_s * backoff_mult**k`` after failed attempt
    ``k``), bounded by ``max_retries`` attempts and a ``retry_timeout_s``
    hard deadline; :meth:`recovery_cost` prices the walk on the
    simulated clock. Exhausting the budget escalates to the next tier
    (stale ride-through, then shard failover).
    """
    name: str
    stale_tolerant: bool = False
    retryable: bool = False
    max_retries: int = 4
    backoff_base_s: float = 0.02
    backoff_mult: float = 2.0
    retry_timeout_s: float = 1.0

    def bytes_per_sync(self, pg: PartitionedGraph, feature_dim: int,
                       dtype_bytes: int = 4,
                       row_overhead_bytes: int = 0) -> int:
        """Wire bytes of one FRESH sync (a stale halo_async serve ships
        zero bytes — it replays recorded tables)."""
        return exchange_bytes(pg, feature_dim, _wire_exchange(self.name),
                              dtype_bytes, row_overhead_bytes)

    def recovery_cost(self, losses: int, sync_cost: float
                      ) -> "Tuple[float, int, bool]":
        """Price recovering ``losses`` consecutive transient losses of
        this exchange: ``(seconds, attempts, succeeded)``. A
        non-retryable exchange fails immediately at zero cost (the
        caller escalates straight past tier 1)."""
        if not self.retryable:
            return 0.0, 0, False
        from repro.core import simulation   # lazy: keep module load light
        return simulation.simulate_retry(
            losses, sync_cost=sync_cost, base=self.backoff_base_s,
            mult=self.backoff_mult, max_attempts=self.max_retries,
            timeout=self.retry_timeout_s)


EXCHANGES.register("halo", ExchangeSpec("halo", retryable=True))
EXCHANGES.register("allgather", ExchangeSpec("allgather", retryable=True))
EXCHANGES.register("halo_async", ExchangeSpec("halo_async",
                                              stale_tolerant=True,
                                              retryable=True))
