"""Pallas launch-geometry lint (the "kernel" analyzer family).

Derives every kernel launch a plan implies — the mesh executor's per-shard
local/halo SpMMs (single + batched, DAQ-fused where the plan quantizes the
halo wire) and the single-program executors' whole-graph SpMM — and lints
them *abstractly*: ``jax.eval_shape`` traces the real jitted wrappers
(``block_spmm`` / ``dequant_spmm`` + batched variants) with
``ShapeDtypeStruct`` operands, so grid/operand divisibility and shape
contracts are checked by the kernels' own assertions without allocating or
executing anything.  On top of tracing: scalar-prefetch table bounds (the
kernels index the source table with NO bounds check), dtype agreement on
the quantized wire against the executor's declared wire format, and a
VMEM/SMEM footprint estimate against the TPU budgets.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.diagnostics import (AnalysisContext, Diagnostic, error,
                                        register_check, warning)
from repro.api.registry import EXECUTORS
from repro.kernels.daq_dequant import dequant_spmm, dequant_spmm_batched
from repro.kernels.gather_aggregate import (block_spmm, block_spmm_batched,
                                            padded_feature_dim,
                                            spmm_vmem_limit)
from repro.runtime.bsp import KERNEL_KINDS

#: VMEM of one TPU v5e core. Each SpMM launch asks for the scoped-VMEM
#: limit its block shapes need (``spmm_vmem_limit``); past this it cannot
#: get it.
VMEM_BUDGET_BYTES = 128 * 1024 * 1024
#: SMEM of one TPU v5e core (1 MiB: a compile for a described v5e refuses
#: scalar-prefetched tables past it). Each SpMM launch prefetches its
#: [VB, M] column and mask tables there, M padded to 128 lanes.
SMEM_BUDGET_BYTES = 1024 * 1024

_KERNELS = {
    "block_spmm": block_spmm,
    "block_spmm_batched": block_spmm_batched,
    "dequant_spmm": dequant_spmm,
    "dequant_spmm_batched": dequant_spmm_batched,
}


@dataclasses.dataclass
class LaunchSpec:
    """One kernel launch the plan implies, reduced to what lint needs."""
    label: str               # e.g. "mesh/halo/batched"
    kernel: str              # key into _KERNELS
    tile_shape: Tuple[int, int, int, int]   # per-shard [VB, M, B, B]
    cols: np.ndarray         # FULL stacked column table (all shards)
    src_rows: int            # padded source-table rows
    out_rows: int            # VB * B
    f: int                   # padded feature width of this launch
    batch: Optional[int] = None       # micro-batch size (None = single)
    wire_dtype: np.dtype = np.dtype(np.float32)   # source-table dtype
    quant: bool = False      # True = dequant-fused (codes + scale/min rows)

    @property
    def block(self) -> int:
        return self.tile_shape[-1]

    def abstract_operands(self):
        """ShapeDtypeStructs matching the kernel wrapper's signature."""
        vb, m, b, _ = self.tile_shape
        S = jax.ShapeDtypeStruct
        blocks = S((vb, m, b, b), jnp.float32)
        cols = S((vb, m), jnp.int32)
        mask = S((vb, m), jnp.float32)
        if self.batch is None:
            table = S((self.src_rows, self.f), self.wire_dtype)
            rows = S((self.src_rows,), jnp.float32)
        else:
            table = S((self.batch, self.src_rows, self.f), self.wire_dtype)
            rows = S((self.batch, self.src_rows), jnp.float32)
        if self.quant:
            return (blocks, cols, mask, table, rows, rows)
        return (blocks, cols, mask, table)

    def expected_out_shape(self) -> Tuple[int, ...]:
        if self.batch is None:
            return (self.out_rows, self.f)
        return (self.batch, self.out_rows, self.f)


def _panel_widths(plan) -> List[int]:
    """Padded feature widths the layer stack feeds the aggregation kernels:
    each layer's input width (the first dim of its 2-D weight leaves)."""
    widths = []
    for p in plan.model.params:
        mats = [a for a in jax.tree_util.tree_leaves(p)
                if getattr(a, "ndim", 0) == 2]
        if mats:
            widths.append(int(mats[0].shape[0]))
    if not widths:
        widths = [plan.graph.feature_dim]
    return sorted({padded_feature_dim(w) for w in widths})


def plan_quantizes_halo(plan) -> bool:
    """Mirror of the mesh executor's DAQ-fusion rule: the halo wire is
    quantized when the kernel path is active and the plan compresses
    uploads with DAQ (see ``_MeshBsp._halo_quant``)."""
    return (plan.partitioned.halo_csr is not None
            and plan.model.kind in KERNEL_KINDS
            and plan.config.compressor.startswith("daq"))


def launches_for_plan(plan, batch_probe: int = 8) -> List[LaunchSpec]:
    """Every distinct kernel launch this plan's serving paths can issue."""
    specs: List[LaunchSpec] = []
    pg = plan.partitioned
    widths = _panel_widths(plan)
    if pg.local_csr is not None and pg.halo_csr is not None:
        quant = plan_quantizes_halo(plan)
        for name, csr in (("local", pg.local_csr), ("halo", pg.halo_csr)):
            is_quant = quant and name == "halo"
            wire = np.dtype(np.uint8) if is_quant else np.dtype(np.float32)
            kern = "dequant_spmm" if is_quant else "block_spmm"
            for f in widths:
                for batch in (None, batch_probe):
                    specs.append(LaunchSpec(
                        label=(f"mesh/{name}/"
                               f"{'batched' if batch else 'single'}/f{f}"),
                        kernel=kern + ("_batched" if batch else ""),
                        tile_shape=csr.blocks.shape[1:],
                        cols=np.asarray(csr.cols),
                        src_rows=csr.src_rows, out_rows=csr.out_rows,
                        f=f, batch=batch, wire_dtype=wire, quant=is_quant))
    backend = EXECUTORS.resolve(plan.config.executor)
    if (not getattr(backend, "needs_block_shards", False)
            and plan.model.kind in KERNEL_KINDS
            and plan.config.aggregation in ("pallas", "auto")):
        from repro.kernels import ops
        csr = ops.block_csr_for(plan.graph)
        for f in widths:
            for batch in (None, batch_probe):
                specs.append(LaunchSpec(
                    label=(f"single/graph/"
                           f"{'batched' if batch else 'single'}/f{f}"),
                    kernel="block_spmm" + ("_batched" if batch else ""),
                    tile_shape=tuple(csr.blocks.shape),
                    cols=np.asarray(csr.cols), src_rows=csr.padded_v,
                    out_rows=csr.padded_v, f=f, batch=batch))
    return specs


@register_check(
    "kernel.grid.divisibility", family="kernel", layer="kernel",
    description="abstract-trace every implied launch through the real "
                "kernel wrappers")
def check_grid_divisibility(ctx: AnalysisContext) -> Iterable[Diagnostic]:
    out = []
    cid = "kernel.grid.divisibility"
    for spec in launches_for_plan(ctx.plan, ctx.batch_probe):
        fn = functools.partial(_KERNELS[spec.kernel], interpret=True)
        try:
            res = jax.eval_shape(fn, *spec.abstract_operands())
        except Exception as e:  # the wrappers assert their grid contract
            out.append(error(
                cid, f"{spec.label}: {spec.kernel} rejects the launch "
                     f"geometry ({type(e).__name__}: {e})", layer="kernel",
                subject=spec.label,
                fix_hint="operand shapes do not divide the kernel grid — "
                         "pad src rows to the 128 tile edge and features "
                         "via padded_feature_dim"))
            continue
        if tuple(res.shape) != spec.expected_out_shape():
            out.append(error(
                cid, f"{spec.label}: traced output {tuple(res.shape)} != "
                     f"expected {spec.expected_out_shape()}",
                layer="kernel", subject=spec.label,
                fix_hint="the block-CSR out_rows disagree with the kernel "
                         "grid — rebuild the shards"))
    return out


@register_check(
    "kernel.prefetch.bounds", family="kernel", layer="kernel",
    description="scalar-prefetched column tables stay inside the padded "
                "source table")
def check_prefetch_bounds(ctx: AnalysisContext) -> Iterable[Diagnostic]:
    out = []
    cid = "kernel.prefetch.bounds"
    seen = set()
    for spec in launches_for_plan(ctx.plan, ctx.batch_probe):
        key = (id(spec.cols), spec.src_rows)
        if key in seen:
            continue
        seen.add(key)
        limit = spec.src_rows // spec.block
        cols = spec.cols
        if cols.size == 0:
            continue
        lo, hi = int(cols.min()), int(cols.max())
        if lo < 0 or hi >= limit:
            out.append(error(
                cid, f"{spec.label}: block_cols span [{lo}, {hi}] but the "
                     f"padded source table has only {limit} column blocks "
                     f"({spec.src_rows} rows / {spec.block}) — the kernel "
                     f"indexes with NO bounds check and would read out of "
                     f"the table", layer="kernel", subject=spec.label,
                fix_hint="rebuild the block-CSR shards; a dirty-shard "
                         "reuse kept tiles whose source space shrank"))
    return out


@register_check(
    "kernel.wire.dtype", family="kernel", layer="kernel",
    description="the quantized halo wire's dtypes match the kernel "
                "contract and the declared wire format")
def check_wire_dtype(ctx: AnalysisContext) -> Iterable[Diagnostic]:
    plan = ctx.plan
    pg = plan.partitioned
    out = []
    cid = "kernel.wire.dtype"
    if pg.halo_csr is None or plan.config.executor != "mesh-bsp":
        return out
    from repro.runtime import bsp
    backend = EXECUTORS.resolve(plan.config.executor)
    try:
        declared = backend.wire_format(plan, plan.config.exchange,
                                       plan.config.aggregation)
    except Exception:
        declared = None
    f = padded_feature_dim(plan.graph.feature_dim)
    payload = jax.ShapeDtypeStruct((pg.boundary_slots, f), jnp.float32)
    codes, scales, mins = jax.eval_shape(bsp._wire_quantize, payload)
    if plan_quantizes_halo(plan):
        if not jnp.issubdtype(codes.dtype, jnp.unsignedinteger):
            out.append(error(
                cid, f"the quantized halo wire carries {codes.dtype} codes "
                     f"— dequant_spmm expects unsigned integer codes and "
                     f"silently mis-decodes anything else", layer="kernel",
                subject="_wire_quantize",
                fix_hint="quantize to uint8 (or another unsigned width) "
                         "before the all_gather"))
        for name, spec in (("scales", scales), ("mins", mins)):
            if spec.dtype != jnp.float32:
                out.append(error(
                    cid, f"halo wire {name} are {spec.dtype}, kernel "
                         f"contract is float32", layer="kernel",
                    subject="_wire_quantize",
                    fix_hint="keep the per-row (scale, min) pair f32"))
        actual = (codes.dtype.itemsize,
                  scales.dtype.itemsize + mins.dtype.itemsize)
        if declared is not None and declared != actual:
            out.append(error(
                cid, f"executor declares wire format {declared} "
                     f"(bytes/feature, bytes/row) but the quantized path "
                     f"ships {actual} — the exchange-bytes accounting and "
                     f"the roofline are lying", layer="kernel",
                subject="wire_format",
                fix_hint="keep _MeshBsp.wire_format in sync with "
                         "bsp._wire_quantize"))
    elif declared is not None and declared != (4, 0):
        out.append(error(
            cid, f"float halo wire declared as {declared}, expected (4, 0)",
            layer="kernel", subject="wire_format",
            fix_hint="non-DAQ plans ship raw float32 boundary rows"))
    return out


@register_check(
    "kernel.vmem.budget", family="kernel", layer="kernel",
    description="per-grid-step VMEM (and SMEM prefetch-table) footprint "
                "fits the TPU budgets")
def check_vmem_budget(ctx: AnalysisContext) -> Iterable[Diagnostic]:
    out = []
    cid = "kernel.vmem.budget"
    for spec in launches_for_plan(ctx.plan, ctx.batch_probe):
        vb, m, b, _ = spec.tile_shape
        f_tile = min(128, spec.f)
        tiles = m * b * b * 4
        panel = spec.src_rows * f_tile * spec.wire_dtype.itemsize
        vmem = spmm_vmem_limit(m, b, spec.src_rows, f_tile, spec.wire_dtype,
                               row_params=spec.quant)
        if vmem > VMEM_BUDGET_BYTES:
            out.append(warning(
                cid, f"{spec.label}: one grid step asks for "
                     f"~{vmem / 2**20:.1f} MiB of VMEM (tiles "
                     f"{tiles / 2**20:.1f} + source panel "
                     f"{panel / 2**20:.1f}, double-buffered) against the "
                     f"{VMEM_BUDGET_BYTES // 2**20} MiB of a v5e core — "
                     f"the launch will fail to lower on hardware",
                layer="kernel", subject=spec.label,
                fix_hint="shard the graph further (smaller per-partition "
                         "source tables) or tile the source panel"))
        smem = 2 * vb * (-(-m // 128) * 128) * 4   # cols + mask tables
        if smem > SMEM_BUDGET_BYTES:
            out.append(warning(
                cid, f"{spec.label}: the scalar-prefetched column and mask "
                     f"tables take {smem / 1024:.0f} KiB against the "
                     f"{SMEM_BUDGET_BYTES // 1024} KiB of SMEM on a v5e core",
                layer="kernel", subject=spec.label,
                fix_hint="the ELL width M is blowing up — repartition "
                         "or densify the shard"))
    return out
