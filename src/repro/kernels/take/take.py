"""Pallas TPU kernels: selection-vector row gather + validity-bitmap expand.

``take`` drives the column-selectivity path: after a WHERE filter produces a
selection vector, every projected column gathers its surviving rows. The
selection vector streams through SMEM and the column stays in HBM, viewed
as rows of one 128-lane tile each (the only row slice Mosaic DMAs out of
HBM); each grid step fills a ``(1024, 128)`` block of aligned output tiles
with 1024 row DMAs steered by the indices (no second pass, no padded row
views).

``bitmap_expand`` turns Arrow's LSB-packed validity bytes into a bool mask
in row order inside VMEM: each (8, 128) block of bytes becomes an (8, 1024)
block of bits. One 0/1 matrix product on the MXU copies byte ``l`` into
lanes ``8l .. 8l+7`` (exact: bytes are integers below 256), and each lane
then keeps its own bit with a shift-and-mask. The kernel writes int8, not
bool: Pallas carries a bool output as int32 in HBM, four times the mask;
the wrapper's ``!= 0`` fuses into the copy that flattens the rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import interpret_mode

LANES = 128
BITS = 8
# output rows per take step: 128 aligned (8, 128) tiles of 32-bit data. The
# selection streams through SMEM a step at a time (a whole one would not fit:
# 2^18 indices fill its 1 MiB), in blocks of XLA's 1-D int32 tile (1024).
ROWS = 1024


def _take_kernel(idx_ref, src_hbm, out_ref, sem, *, k: int):
    # idx_ref holds this step's ROWS indices in SMEM; src_hbm is the column
    # viewed as (n_rows * k, 128): row r's lane tile j is view row r*k + j,
    # so every DMA moves one whole 128-lane view row. All ROWS copies have
    # one size and share one semaphore.
    j = pl.program_id(1)

    def copy(r):
        return pltpu.make_async_copy(
            src_hbm.at[pl.ds(idx_ref[r] * k + j, 1), :],
            out_ref.at[pl.ds(r, 1), :], sem)

    def start(r, carry):
        copy(r).start()
        return carry

    def wait(r, carry):
        copy(r).wait()
        return carry

    jax.lax.fori_loop(0, ROWS, start, 0)
    jax.lax.fori_loop(0, ROWS, wait, 0)


def take_rows(values: jax.Array, indices: jax.Array) -> jax.Array:
    """out[i] = values[indices[i]]. values: (n_rows, width) of a 32-bit
    dtype with width a multiple of 128; indices: (n_out,) int32 with n_out
    a multiple of 1024."""
    return _take_rows(values, indices, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _take_rows(values: jax.Array, indices: jax.Array, *,
               interpret: bool) -> jax.Array:
    n_rows, width = values.shape
    n_out = indices.shape[0]
    k = width // LANES
    return pl.pallas_call(
        functools.partial(_take_kernel, k=k),
        grid=(n_out // ROWS, k),
        in_specs=[pl.BlockSpec((ROWS,), lambda i, j: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i, j: (i, j)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((n_out, width), values.dtype),
        interpret=interpret,
    )(indices, values.reshape(n_rows * k, LANES))


def _bitmap_kernel(bm_ref, out_ref):
    bytes_ = bm_ref[...].astype(jnp.int32).astype(jnp.float32)   # (8, 128)
    shape = (LANES, LANES * BITS)
    spread = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) // BITS
              == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    # lane 8l + b of a row holds byte l of that row, then keeps its bit b
    spread_bytes = jnp.dot(bytes_, spread.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    bit = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1) % BITS
    bits = (spread_bytes.astype(jnp.int32) >> bit) & 1
    out_ref[...] = bits.astype(out_ref.dtype)


def bitmap_expand(bitmap: jax.Array) -> jax.Array:
    """LSB-packed bits -> bool. bitmap: (n_bytes,) uint8 with n_bytes a
    multiple of 8*128; -> (n_bytes * 8,) bool."""
    return _bitmap_expand(bitmap, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bitmap_expand(bitmap: jax.Array, *, interpret: bool) -> jax.Array:
    rows = bitmap.shape[0] // LANES
    bits = pl.pallas_call(
        _bitmap_kernel,
        grid=(rows // 8,),
        in_specs=[pl.BlockSpec((8, LANES), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((8, LANES * BITS), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES * BITS), jnp.int8),
        interpret=interpret,
    )(bitmap.reshape(rows, LANES))
    return bits.reshape(-1) != 0
