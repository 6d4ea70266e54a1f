"""Pallas TPU kernels: tile-routed segment pack / unpack.

This is the *baseline's* serialization memcpy expressed as a TPU kernel (the
cost Thallus deletes) plus its inverse. Both are pure data-movement kernels:
grid = one step per tile, the routing table (which segment / which tile)
rides in scalar-prefetch SMEM so the BlockSpec ``index_map`` can steer the
HBM→VMEM DMA directly — the copy itself is a single VMEM tile assignment,
i.e. the kernel runs at DMA speed, which is the roofline for serialization.

Block shape: (TILE_ROWS=32, TILE_LANES=128) uint8 — the minimal aligned tile
for 8-bit data on TPU, 4 KiB per step, well under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import interpret_mode
from .ref import TILE_LANES, TILE_ROWS


def _copy_kernel(seg_ids, tile_ids, src_ref, out_ref):
    # Routing already happened in the index_map; the body is the DMA'd copy.
    # src block is (1, 1, 32, 128); out block is (1, 32, 128).
    out_ref[...] = src_ref[0]


def pack_tiles(src: jax.Array, seg_ids: jax.Array,
               tile_ids: jax.Array) -> jax.Array:
    """Gather routed tiles: out[t] = src[seg_ids[t], tile_ids[t]].

    src: (n_seg, max_tiles, 32, 128) uint8
    seg_ids/tile_ids: (n_out_tiles,) int32 scalar-prefetch routing table
    -> (n_out_tiles, 32, 128) uint8 packed buffer
    """
    return _pack_tiles(src, seg_ids, tile_ids, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_tiles(src: jax.Array, seg_ids: jax.Array, tile_ids: jax.Array,
                *, interpret: bool) -> jax.Array:
    n_out = seg_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_out,),
        in_specs=[
            pl.BlockSpec((1, 1, TILE_ROWS, TILE_LANES),
                         lambda t, seg_ids, tile_ids: (seg_ids[t], tile_ids[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_ROWS, TILE_LANES),
                               lambda t, seg_ids, tile_ids: (t, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, TILE_ROWS, TILE_LANES), jnp.uint8),
        interpret=interpret,
    )(seg_ids, tile_ids, src)


def unpack_tiles(packed: jax.Array, gather_ids: jax.Array,
                 *, n_seg: int, max_tiles: int) -> jax.Array:
    """Inverse gather: out[s, t] = packed[gather_ids[s*max_tiles + t]].

    ``gather_ids`` is the *inverse* routing table (see
    :func:`repro.kernels.pack.ops.inverse_routing`); padding tiles point at a
    zero tile appended past the packed payload, so the kernel stays a pure
    gather — every output tile is written exactly once, no scatter hazards.
    packed: (n_out_tiles + 1, 32, 128) with packed[-1] == 0.
    """
    return _unpack_tiles(packed, gather_ids, n_seg=n_seg,
                         max_tiles=max_tiles, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("n_seg", "max_tiles", "interpret"))
def _unpack_tiles(packed: jax.Array, gather_ids: jax.Array, *, n_seg: int,
                  max_tiles: int, interpret: bool) -> jax.Array:
    n_total = n_seg * max_tiles

    def kernel(gather_ids, packed_ref, out_ref):
        # packed block (1, 32, 128) -> out block (1, 1, 32, 128).
        out_ref[...] = packed_ref[...][None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_total,),
        in_specs=[
            pl.BlockSpec((1, TILE_ROWS, TILE_LANES),
                         lambda t, gather_ids: (gather_ids[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TILE_ROWS, TILE_LANES),
                               lambda t, gather_ids: (t // max_tiles, t % max_tiles, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_seg, max_tiles, TILE_ROWS, TILE_LANES),
                                       jnp.uint8),
        interpret=interpret,
    )(gather_ids, packed)
    return out
