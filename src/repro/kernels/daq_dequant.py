"""Pallas TPU kernels for degree-aware-quantized feature streaming.

The paper's DAQ (§III-D) shrinks the *device -> fog* link payload. The TPU
analogue of that bottleneck is HBM bandwidth: storing vertex features
quantized in HBM and dequantizing inside VMEM tiles cuts the memory-roofline
term of the aggregation by the compression ratio.

Two kernels:
  * ``dequant``        — standalone row-wise linear dequantization
                         out[v,f] = codes[v,f] * scale[v] + min[v]
  * ``dequant_spmm``   — BEYOND-PAPER fusion: block-CSR aggregation directly
                         over quantized features; the dense feature panel
                         never materializes in HBM (dequantized per VMEM
                         tile right before the MXU matmul).

Both validated in interpret mode against repro.kernels.ref oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_aggregate import BLOCK, spmm_vmem_limit


def _as_f32(codes):
    """Unsigned codes -> f32. Mosaic has no unsigned-to-float cast, so go
    through int32: exact for every code below 2**31, the same values as a
    direct cast (DAQ codes are at most 16 bits, the halo wire's 8)."""
    return codes.astype(jnp.int32).astype(jnp.float32)


def _row_params(scales, mins):
    """Pack per-row (scale, min) as one lane-major [..., 2, V] table.

    Mosaic refuses 1-D row-parameter blocks (their tiled layout disagrees
    with XLA's) and would pad a [V, 1] column to 128 lanes in VMEM; a
    [2, V] table is dense, sliced on the lane axis at 128-aligned offsets,
    and transposed in-kernel to per-row columns (see ``_row_columns``)."""
    return jnp.stack([scales, mins], axis=-2)


def _row_columns(sm):
    """[2, R] lane-major (scale, min) slice -> two [R, 1] columns (exact)."""
    t = jnp.transpose(sm)
    return t[:, 0:1], t[:, 1:2]


def _dequant_kernel(codes_ref, sm_ref, out_ref):
    """One (v_tile, f_tile) VMEM tile: out = codes * scale[row] + min[row]."""
    codes = _as_f32(codes_ref[...])
    sc, mn = _row_columns(sm_ref[...])
    out_ref[...] = codes * sc + mn


@functools.partial(jax.jit, static_argnames=("v_tile", "f_tile", "interpret"))
def dequant(codes: jnp.ndarray, scales: jnp.ndarray, mins: jnp.ndarray, *,
            v_tile: int = 256, f_tile: int = 128,
            interpret: bool = True) -> jnp.ndarray:
    """Row-wise linear dequantization, tiled (v_tile x f_tile) over VMEM."""
    v, f = codes.shape
    v_tile = min(v_tile, v)
    f_tile = min(f_tile, f)
    assert v % v_tile == 0 and f % f_tile == 0, (codes.shape, v_tile, f_tile)
    grid = (v // v_tile, f // f_tile)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_tile, f_tile), lambda i, j: (i, j)),
            pl.BlockSpec((2, v_tile), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((v_tile, f_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((v, f), jnp.float32),
        interpret=interpret,
    )(codes, _row_params(scales, mins))


def _dequant_spmm_kernel(cols_ref, mask_ref, blocks_ref, codes_ref, sm_ref,
                         out_ref, *, m: int, block: int):
    """One (row-block, feature-tile[, batch]) grid step: the [B, TF] source
    panel is dequantized in VMEM right before each MXU matmul, so the dense
    feature table never materializes in HBM. ``cols_ref``/``mask_ref`` are
    the whole scalar-prefetched [VB, M] tables (SMEM), as in
    ``gather_aggregate._spmm_kernel``."""
    i = pl.program_id(0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)

    def body(k, acc):
        tile = blocks_ref[k]                                    # [B, B]
        start = pl.multiple_of(cols_ref[i, k] * block, block)
        codes = codes_ref[pl.dslice(start, block), :]           # [B, TF]
        sc, mn = _row_columns(sm_ref[:, pl.dslice(start, block)])  # [B, 1]
        panel = _as_f32(codes) * sc + mn
        return acc + mask_ref[i, k] * jnp.dot(
            tile, panel, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, m, body, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "f_tile", "interpret"))
def dequant_spmm(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                 block_mask: jnp.ndarray, codes: jnp.ndarray,
                 scales: jnp.ndarray, mins: jnp.ndarray, *,
                 block: int = BLOCK, f_tile: int = 128,
                 interpret: bool = True) -> jnp.ndarray:
    """out = A @ dequant(codes): fused aggregation over quantized features.

    Same block layout as ``gather_aggregate.block_spmm`` (including the
    rectangular case: ``codes`` is the source table, any multiple of
    ``block`` rows covering every ``block_cols`` entry; the output has
    ``vb * block`` rows). ``codes`` is an unsigned-int array (uint8/16/32),
    ``scales``/``mins`` are f32[v] row parameters; zero-padded source rows
    (codes == 0, scale == min == 0) dequantize to exactly 0 and therefore
    contribute nothing. Output is f32.
    """
    vb, m, b, _ = blocks.shape
    v, f = codes.shape
    assert b == block and v % block == 0
    f_tile = min(f_tile, f)
    assert f % f_tile == 0
    grid = (vb, f // f_tile)
    kernel = functools.partial(_dequant_spmm_kernel, m=m, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_cols, block_mask: SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, m, block, block),
                         lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((v, f_tile), lambda i, j, *_: (0, j)),  # codes panel
            pl.BlockSpec((2, v), lambda i, j, *_: (0, 0)),       # scale, min
        ],
        out_specs=pl.BlockSpec((block, f_tile), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vb * block, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=spmm_vmem_limit(m, block, v, f_tile, codes.dtype,
                                             row_params=True)),
        interpret=interpret,
    )(block_cols, block_mask, blocks, codes, _row_params(scales, mins))


@functools.partial(jax.jit, static_argnames=("block", "f_tile", "interpret"))
def dequant_spmm_batched(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                         block_mask: jnp.ndarray, codes: jnp.ndarray,
                         scales: jnp.ndarray, mins: jnp.ndarray, *,
                         block: int = BLOCK, f_tile: int = 128,
                         interpret: bool = True) -> jnp.ndarray:
    """out[b] = A @ dequant(codes[b]): the fused kernel over a quantized
    [B, V, F] feature stack (``scales``/``mins`` are f32[B, V]).

    Batch-axis variant of :func:`dequant_spmm`, mirroring
    ``block_spmm_batched``: one dispatch for the whole micro-batch, shared
    block-CSR operands, B innermost in the grid so adjacency tiles amortize
    across the batch. It runs the unbatched kernel body, so per-element
    results are bit-identical to ``dequant_spmm``.
    """
    vb, m, blk, _ = blocks.shape
    b, v, f = codes.shape
    assert blk == block and v % block == 0
    assert scales.shape == mins.shape == (b, v), (scales.shape, codes.shape)
    f_tile = min(f_tile, f)
    assert f % f_tile == 0
    grid = (vb, f // f_tile, b)
    kernel = functools.partial(_dequant_spmm_kernel, m=m, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_cols, block_mask: SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, m, block, block),
                         lambda i, j, k, *_: (i, 0, 0, 0)),
            pl.BlockSpec((None, v, f_tile),
                         lambda i, j, k, *_: (k, 0, j)),     # codes[b]
            pl.BlockSpec((None, 2, v),
                         lambda i, j, k, *_: (k, 0, 0)),     # scale, min
        ],
        out_specs=pl.BlockSpec((None, block, f_tile),
                               lambda i, j, k, *_: (k, i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, vb * block, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=spmm_vmem_limit(m, block, v, f_tile, codes.dtype,
                                             row_params=True)),
        interpret=interpret,
    )(block_cols, block_mask, blocks, codes, _row_params(scales, mins))
