"""jit'd public wrappers around the pack/unpack Pallas kernels.

``pack_segments`` is the end-to-end on-device serialize: numpy/JAX buffers →
staged ragged-2D form → tile-routed gather → one contiguous packed buffer.
``unpack_segments`` reverses it. These are the device analogues of
:func:`repro.core.serialize.pack` / ``unpack`` and the benchmark units for
the serialization-overhead measurements.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .pack import pack_tiles, unpack_tiles
from .ref import (TILE_BYTES, TILE_LANES, TILE_ROWS, layout_segments,
                  stage_segments, tiles_for)


def routing(seg_lens: list[int]) -> tuple[np.ndarray, np.ndarray]:
    seg_ids, tile_ids, _ = layout_segments(seg_lens)
    return seg_ids, tile_ids


def inverse_routing(seg_lens: list[int], max_tiles: int) -> np.ndarray:
    """gather_ids[s*max_tiles + t] = packed index of (s, t), or the zero-tile
    sentinel (== n_out_tiles) for ragged padding."""
    seg_ids, tile_ids, n_out = layout_segments(seg_lens)
    n_seg = len(seg_lens)
    inv = np.full(n_seg * max_tiles, n_out, dtype=np.int32)
    for packed_idx, (s, t) in enumerate(zip(seg_ids, tile_ids)):
        inv[s * max_tiles + t] = packed_idx
    return inv


def pack_segments(segments: list[np.ndarray]) -> tuple[jax.Array, list[int]]:
    """Serialize: list of arbitrary-dtype buffers -> (packed uint8 tiles,
    per-segment byte lengths). packed shape: (n_out_tiles, 32, 128)."""
    staged, seg_lens = stage_segments(segments)
    seg_ids, tile_ids = routing([int(n) for n in seg_lens])
    packed = pack_tiles(jnp.asarray(staged), jnp.asarray(seg_ids),
                        jnp.asarray(tile_ids))
    return packed, [int(n) for n in seg_lens]


def unpack_segments(packed: jax.Array,
                    seg_lens: list[int]) -> list[np.ndarray]:
    """Deserialize: packed tiles + size vector -> per-segment uint8 buffers
    (caller re-views dtypes, as in Arrow's buffers+sizes+dtypes assembly)."""
    max_tiles = max(tiles_for(n) for n in seg_lens)
    inv = inverse_routing(seg_lens, max_tiles)
    zero = jnp.zeros((1, TILE_ROWS, TILE_LANES), jnp.uint8)
    padded = jnp.concatenate([packed, zero], axis=0)
    ragged = unpack_tiles(padded, jnp.asarray(inv), n_seg=len(seg_lens),
                          max_tiles=max_tiles)
    out = []
    for i, n in enumerate(seg_lens):
        flat = np.asarray(ragged[i]).reshape(-1)
        out.append(flat[:n])
    return out


def packed_nbytes(seg_lens: list[int]) -> int:
    return sum(tiles_for(n) for n in seg_lens) * TILE_BYTES
