"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler builds for a topology that is described
and not attached, and refuses what the chip would refuse (block shapes
Mosaic cannot tile, casts it cannot lower, more VMEM than a kernel may
use). Interpret-mode tests cannot see any of that.

Widths: SIoT at full scale on one chip (16,216 vertices -> VB = M = 127
row/tile blocks, F = 52) and the widest operand of a 4-fog ``"1A+2B+1C"``
shard of it (VB = 47 row-blocks, M = 68 tiles, 14,336 halo source rows).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.daq_dequant import (dequant, dequant_spmm,
                                       dequant_spmm_batched)
from repro.kernels.gather_aggregate import (BLOCK, block_spmm,
                                            block_spmm_batched)

F = 52        # SIoT feature width
BATCH = 8     # Server's default max_batch
# name -> (row-blocks VB, tiles per row-block M, source rows)
WIDTHS = {"siot_full": (127, 127, 127 * BLOCK),
          "shard_of_4": (47, 68, 14336)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _operands(kernel: str, width: str, sharding):
    vb, m, src = WIDTHS[width]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    csr = (s((vb, m, BLOCK, BLOCK), jnp.float32), s((vb, m), jnp.int32),
           s((vb, m), jnp.float32))
    if kernel == "block_spmm":
        return block_spmm, csr + (s((src, F), jnp.float32),)
    if kernel == "block_spmm_batched":
        return block_spmm_batched, csr + (s((BATCH, src, F), jnp.float32),)
    if kernel == "dequant_spmm":
        return dequant_spmm, csr + (s((src, F), jnp.uint8),
                                    s((src,), jnp.float32),
                                    s((src,), jnp.float32))
    if kernel == "dequant_spmm_batched":
        return dequant_spmm_batched, csr + (
            s((BATCH, src, F), jnp.uint8), s((BATCH, src), jnp.float32),
            s((BATCH, src), jnp.float32))
    # dequant tiles rows by 256: pad the source rows up to that multiple,
    # as ops.dequantize_features does.
    rows = -(-src // 256) * 256
    return dequant, (s((rows, F), jnp.uint8), s((rows,), jnp.float32),
                     s((rows,), jnp.float32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["block_spmm", "block_spmm_batched",
                                    "dequant", "dequant_spmm",
                                    "dequant_spmm_batched"])
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, args = _operands(kernel, width, one_chip)
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
