"""Pallas TPU kernel: blocked CSR neighbor aggregation (the GNN hot-spot).

TPU adaptation of the paper's kernel-level aggregation (Fograph §III-E wraps
PyG's CUDA gather/scatter kernels). GPU gather/scatter does not transfer to
the TPU's systolic MXU, so we *re-block* the computation:

  * the adjacency is laid out as block-CSR: dense B x B tiles (B = 128,
    MXU-native) listed per row-block (ELL-padded to M tiles per row-block);
  * aggregation out = A @ H becomes a sequence of MXU matmuls
    acc += tile[m] @ H[cols[m]] — every operand is a VMEM-resident,
    128-aligned tile; the irregular gather collapses to *block-row* dynamic
    slices instead of per-edge scatter.

VMEM budget per grid step: M·B·B·4 (tiles) + V·TF·4 (feature panel)
+ B·TF·4 (acc). The feature panel is tiled on F only — the kernel targets
per-partition local graphs (Fograph shards the global graph across fogs), so
V here is |V|/n_fogs and the panel fits VMEM for the paper's scales.

Kernel body is validated in interpret mode on CPU against ref.block_spmm_ref.

Both SpMM kernels come in two flavours: the single-query [V, F] form and a
[B, V, F] *feature-stack* form (``block_spmm_batched``) that serves a whole
serving micro-batch in one fused dispatch — B is an extra (fastest-varying)
grid axis so the block-CSR operand loads amortize across the batch. Both
share one kernel body and scalar-prefetch the ``block_cols``/``block_mask``
tables into SMEM (``PrefetchScalarGridSpec``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # MXU-native tile edge


def padded_feature_dim(f: int) -> int:
    """Feature count the SpMM kernels' f-tiling accepts for ``f`` columns.

    ``f_tile`` is clamped to ``min(128, f)``, so any ``f <= 128`` passes
    unpadded; wider tables must be a multiple of the 128-lane tile.
    """
    return f if f <= 128 else -(-f // 128) * 128


def build_block_csr(senders: np.ndarray, receivers: np.ndarray,
                    num_vertices: int, block: int = BLOCK,
                    weights: np.ndarray = None):
    """Host-side: COO edges -> ELL-over-blocks block-CSR.

    Layout contract (shared by ``block_spmm`` and ``dequant_spmm``):

      * The output-row space is ``receivers`` (``num_vertices`` rows,
        padded up to ``VB = ceil(num_vertices / block)`` row-blocks).
      * The source-column space is ``senders`` and may be a *different*
        index space (e.g. a gathered halo table): column-block ids are
        ``senders // block``, unbounded by ``num_vertices``. The feature
        table handed to the SpMM must cover ``(max(senders)//block + 1)
        * block`` rows (zero-pad to a multiple of ``block``).
      * Each row-block lists exactly ``M`` tiles (ELL padding): real tiles
        carry ``block_mask == 1``, padding tiles are all-zero with
        ``block_mask == 0`` and ``block_cols == 0`` (they multiply the
        first source panel by a zero tile — harmless but not free).
      * Duplicate edges accumulate (tile entries count multiplicity), and
        ``weights`` (f32[E], default 1) scales each edge's contribution —
        e.g. 1/deg(receiver) bakes mean-aggregation into the adjacency.

    Returns ``(blocks f32[VB, M, B, B], block_cols i32[VB, M],
    block_mask f32[VB, M], padded_v = VB * block)``. Zero edges are legal
    and yield a single all-padding tile per row-block (M == 1).
    """
    vb = -(-num_vertices // block)
    padded_v = vb * block
    if weights is None:
        weights = np.ones(len(senders), np.float32)
    rb = receivers // block
    cb = senders // block
    # Unique (row-block, col-block) pairs. The column-block count follows
    # the senders' index space, which may be wider than the row space.
    ncb = int(cb.max()) + 1 if len(cb) else 1
    key = rb.astype(np.int64) * ncb + cb
    uniq, inv = np.unique(key, return_inverse=True)
    nb = len(uniq)
    tiles = np.zeros((nb, block, block), np.float32)
    np.add.at(tiles, (inv, receivers % block, senders % block), weights)
    tile_rb = (uniq // ncb).astype(np.int64)
    tile_cb = (uniq % ncb).astype(np.int32)
    counts = np.bincount(tile_rb, minlength=vb)
    m = max(1, int(counts.max()))
    blocks = np.zeros((vb, m, block, block), np.float32)
    block_cols = np.zeros((vb, m), np.int32)
    block_mask = np.zeros((vb, m), np.float32)
    slot = np.zeros(vb, np.int64)
    for t in range(nb):
        i = tile_rb[t]
        j = slot[i]
        blocks[i, j] = tiles[t]
        block_cols[i, j] = tile_cb[t]
        block_mask[i, j] = 1.0
        slot[i] += 1
    return blocks, block_cols, block_mask, padded_v


#: Mosaic's default scoped-VMEM limit; a kernel is never given less.
_DEFAULT_SCOPED_VMEM = 16 * 2**20
#: Headroom for Mosaic's own scratch on top of the blocks counted below.
_VMEM_HEADROOM = 4 * 2**20


def _vmem_block_bytes(shape, dtype) -> int:
    """VMEM bytes of one block: its last two dims pad to the native tile
    (8 sublanes of 32-bit words, 32 rows of 8-bit; 128 lanes)."""
    itemsize = np.dtype(dtype).itemsize
    *lead, r, c = shape
    sub = 8 * 4 // itemsize
    return (int(np.prod(lead, dtype=np.int64)) * (-(-r // sub) * sub)
            * (-(-c // 128) * 128) * itemsize)


def spmm_vmem_limit(m: int, block: int, src_rows: int, f_tile: int,
                    src_dtype=jnp.float32, row_params: bool = False) -> int:
    """Scoped-VMEM limit of one SpMM grid step, from its block shapes.

    Every pipelined block is double-buffered::

        2 * ( M*B*B*4                     adjacency tiles
            + pad(V)*pad(TF)*itemsize     source panel (f32 or uint8 codes)
            [ + 8*V*4 ]                   (scale, min) rows, DAQ kernels
            + B*pad(TF)*4 )               output tile
        + 2 * B*pad(TF)*4                 accumulator + one dot result
        + 4 MiB                           Mosaic scratch headroom

    At SIoT full width (M = 127, V = 127*128 = 16256, TF = 52 -> 128 lanes,
    f32 source) that is 2*(8,323,072 + 8,323,072 + 65,536) + 131,072
    + 4,194,304 = 37,748,736 bytes: over the 16 MiB default, well inside
    the 128 MiB of VMEM on a v5e chip.
    """
    blocks = _vmem_block_bytes((m, block, block), jnp.float32)
    panel = _vmem_block_bytes((src_rows, f_tile), src_dtype)
    params = _vmem_block_bytes((2, src_rows), jnp.float32) if row_params else 0
    out = _vmem_block_bytes((block, f_tile), jnp.float32)
    need = 2 * (blocks + panel + params + out) + 2 * out + _VMEM_HEADROOM
    return max(_DEFAULT_SCOPED_VMEM, need)


def _spmm_kernel(cols_ref, mask_ref, blocks_ref, h_ref, out_ref, *, m: int,
                 block: int):
    """One (row-block, feature-tile[, batch]) grid step.

    ``cols_ref`` / ``mask_ref`` are the *whole* [VB, M] tile tables,
    scalar-prefetched into SMEM once per launch and indexed by the row-block
    id. Mosaic cannot tile a ``(None, M)`` VMEM block of them (the last two
    block dims must be (8, 128)-aligned or span the array), and SMEM is where
    a per-tile scalar belongs anyway. With a batch axis it iterates fastest,
    so a block row's adjacency tiles are fetched once for all B stacks.
    """
    i = pl.program_id(0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)

    def body(k, acc):
        tile = blocks_ref[k]                      # [B, B]
        start = pl.multiple_of(cols_ref[i, k] * block, block)
        panel = h_ref[pl.dslice(start, block), :]         # [B, TF]
        return acc + mask_ref[i, k] * jnp.dot(
            tile, panel, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, m, body, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "f_tile", "interpret"))
def block_spmm_batched(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                       block_mask: jnp.ndarray, h: jnp.ndarray, *,
                       block: int = BLOCK, f_tile: int = 128,
                       interpret: bool = True) -> jnp.ndarray:
    """out[b] = A @ h[b] for a [B, V, F] feature stack — one fused dispatch.

    Batch-axis variant of :func:`block_spmm`: the same ELL-block-CSR
    operands serve every element of the micro-batch, with the batch as an
    extra (fastest-varying) grid dimension so the adjacency tiles loaded
    for a block row are amortized across all B stacks. It runs the same
    kernel body as the unbatched form, so each ``out[b]`` is bit-identical
    to ``block_spmm(..., h[b])``.
    """
    vb, m, blk, _ = blocks.shape
    b, v, f = h.shape
    assert blk == block and v % block == 0, (blocks.shape, h.shape)
    f_tile = min(f_tile, f)
    assert f % f_tile == 0, (f, f_tile)
    grid = (vb, f // f_tile, b)
    kernel = functools.partial(_spmm_kernel, m=m, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_cols, block_mask: SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, m, block, block),
                         lambda i, j, k, *_: (i, 0, 0, 0)),          # tiles
            pl.BlockSpec((None, v, f_tile),
                         lambda i, j, k, *_: (k, 0, j)),             # h[b]
        ],
        out_specs=pl.BlockSpec((None, block, f_tile),
                               lambda i, j, k, *_: (k, i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, vb * block, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=spmm_vmem_limit(m, block, v, f_tile)),
        interpret=interpret,
    )(block_cols, block_mask, blocks, h)


@functools.partial(jax.jit, static_argnames=("block", "f_tile", "interpret"))
def block_spmm(blocks: jnp.ndarray, block_cols: jnp.ndarray,
               block_mask: jnp.ndarray, h: jnp.ndarray, *,
               block: int = BLOCK, f_tile: int = 128,
               interpret: bool = True) -> jnp.ndarray:
    """out = A @ h with A in ELL-block-CSR layout (see build_block_csr).

    ``A`` may be rectangular: ``h`` is the *source* table (``v`` rows, any
    multiple of ``block`` covering every ``block_cols`` entry) while the
    output has ``vb * block`` rows — the shard-local serving path feeds a
    local+halo source table that is wider than the shard's own row space.
    ``h`` must be f32 with ``f % f_tile == 0`` (``f_tile`` is clamped to
    ``f``, so any ``f <= 128`` needs no feature padding); output is f32.
    """
    vb, m, b, _ = blocks.shape
    v, f = h.shape
    assert b == block and v % block == 0, (blocks.shape, h.shape)
    f_tile = min(f_tile, f)
    assert f % f_tile == 0, (f, f_tile)
    grid = (vb, f // f_tile)
    kernel = functools.partial(_spmm_kernel, m=m, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_cols, block_mask: SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, m, block, block),
                         lambda i, j, *_: (i, 0, 0, 0)),             # tiles
            pl.BlockSpec((v, f_tile), lambda i, j, *_: (0, j)),      # h panel
        ],
        out_specs=pl.BlockSpec((block, f_tile), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vb * block, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=spmm_vmem_limit(m, block, v, f_tile)),
        interpret=interpret,
    )(block_cols, block_mask, blocks, h)
