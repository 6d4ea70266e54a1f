"""jit'd wrappers: padding/shape management for take + bitmap_expand."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .ref import bitmap_expand_ref, take_ref  # noqa: F401 (re-export oracles)
from .take import LANES, ROWS, bitmap_expand, take_rows

_BM_ALIGN = 8 * LANES  # bitmap kernel granularity in bytes


def _as_words(values: jax.Array) -> jax.Array:
    """(n, w) column of 1-, 2- or 4-byte values -> (n, 128·k) 32-bit words,
    zero-padded; each row's bytes stay in that row."""
    n, w = values.shape
    per_word = 4 // values.dtype.itemsize
    w_pad = -w % (LANES * per_word)
    if w_pad:
        values = jnp.pad(values, ((0, 0), (0, w_pad)))
    if per_word > 1:
        values = values.reshape(n, -1, per_word)
    return jax.lax.bitcast_convert_type(values, jnp.uint32)


def take_column(values: np.ndarray | jax.Array,
                indices: np.ndarray | jax.Array) -> jax.Array:
    """Row-gather a 1-D or 2-D fixed-width column by a selection vector.
    Out-of-range indices read as in ``take_ref``. Carries the rows as
    128-lane tiles of 32-bit words, pads the selection to whole output
    tiles, and restores dtype and shape."""
    values = jnp.asarray(values)
    indices = jnp.asarray(indices, jnp.int32)
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, w = values.shape
    n_sel, dtype = indices.shape[0], values.dtype
    # indices as jnp reads them: negative from the end, the rest clamped; the
    # kernel's row DMAs must never leave the column
    indices = jnp.clip(jnp.where(indices < 0, indices + n, indices), 0, n - 1)
    sel_pad = -n_sel % ROWS
    if sel_pad:
        indices = jnp.pad(indices, (0, sel_pad))
    words = take_rows(_as_words(values), indices)[:n_sel]
    out = jax.lax.bitcast_convert_type(words, dtype).reshape(n_sel, -1)[:, :w]
    return out[:, 0] if squeeze else out


def expand_validity(bitmap: np.ndarray | jax.Array,
                    num_rows: int) -> jax.Array:
    """Arrow validity bitmap -> bool mask of length num_rows."""
    bitmap = jnp.asarray(bitmap, jnp.uint8)
    pad = -bitmap.shape[0] % _BM_ALIGN
    if pad:
        bitmap = jnp.pad(bitmap, (0, pad))
    mask = bitmap_expand(bitmap)
    return mask[:num_rows]
