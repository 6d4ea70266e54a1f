"""Device-side Thallus: host↔HBM and HBM↔HBM columnar movement.

The TPU-native translation of the paper's two paths:

* **thallus path** (`batch_to_device`): the batch on device is a *pytree*
  of per-column arrays (logical assembly, like Arrow's zero-copy
  deserialize), and no staging buffer ever exists. A batch pulled by the
  Thallus client already sits in one receive region
  (``bulk.allocate_like``: every segment a view into one buffer). Such a
  batch lands as ONE transfer of the region's words, the span from its
  first column to the end of its last, and one jitted split cuts each
  column out on the device (a slice of its words and a bitcast). Every
  other batch (fresh arrays, a pooled slab per segment, a mesh, a dtype
  the split does not carry) goes host→device column by column via
  ``jax.device_put`` with an explicit ``NamedSharding``, the
  scatter-gather DMA analogue. The split compiles once per region layout,
  so a layout lands column by column the first time it is seen and as
  one transfer from its second sighting on.
* **rpc path** (`batch_to_device_packed`): serialize into ONE contiguous
  host buffer (full copy), ship that single buffer, then slice columns back
  out *on device* (more copies). This is the baseline whose cost the
  protocol deletes.

Both produce bit-identical column arrays (tests assert it), so the rest of
the stack — the input pipeline feeding ``train_step`` — is transport-
agnostic.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import spans
from . import serialize
from .recordbatch import RecordBatch


@dataclasses.dataclass
class DeviceBatch:
    """A record batch on device: dict of column-name → array pytree."""

    columns: dict[str, jax.Array]
    num_rows: int

    def __getitem__(self, name: str) -> jax.Array:
        return self.columns[name]


def _check_device_column(col) -> None:
    """Refuse, by name, a column the device would not hold bit for bit:
    variable-length, or a 64-bit dtype with x64 off (silently narrowed)."""
    if col.field.varlen:
        raise ValueError(
            f"column {col.field.name!r} is variable-length; device transport "
            "carries fixed-width (tokenized/numeric) columns")
    dtype = col.values.dtype
    if jax.dtypes.canonicalize_dtype(dtype) != dtype:
        raise ValueError(
            f"column {col.field.name!r} is {dtype}, which the device would "
            f"hold as {jax.dtypes.canonicalize_dtype(dtype)}; device "
            "transport carries only columns it lands bit for bit")


# The dtypes the split cuts out of uint32 words bit for bit on a TPU v5e.
# float16 is not one: a float16 cut there turns every NaN into 0x7e00.
_SPLIT_DTYPES = frozenset(map(np.dtype, (np.int8, np.uint8, np.int16,
                                         np.uint16, np.int32, np.uint32,
                                         np.float32)))
# A region's words are shipped from the first column to the end of the
# last; only where they are mostly the columns' own bytes (a few rows
# sliced out of each column of a large region would ship all of it).
_REGION_SLACK = 256          # bytes per column: alignment gaps, validity


def _region(values: list[np.ndarray]):
    """``(words, layout)`` where every column's values are a C-contiguous
    view into one host buffer at a 4-byte-aligned offset, in a dtype the
    split carries: the buffer's uint32 words spanning the columns, and per
    column its ``(word offset, rows, dtype)`` in them. Else ``None``."""
    if len(values) < 2:          # one column is one transfer either way
        return None
    base = values[0].base
    if not isinstance(base, np.ndarray) or not base.flags.c_contiguous:
        return None
    start = base.__array_interface__["data"][0]
    offsets = []
    for v in values:
        offset = v.__array_interface__["data"][0] - start
        if (v.base is not base or not v.flags.c_contiguous
                or v.dtype not in _SPLIT_DTYPES or offset % 4):
            return None
        offsets.append(offset)
    lo = min(offsets)
    hi = lo + -(-(max(o + v.nbytes for o, v in zip(offsets, values)) - lo)
                // 4) * 4
    if (hi > base.nbytes or hi - lo > 2 * sum(v.nbytes for v in values)
            + _REGION_SLACK * len(values)):
        return None
    words = base.reshape(-1).view(np.uint8)[lo:hi].view(np.uint32)
    return words, tuple(((o - lo) // 4, v.size, v.dtype)
                        for o, v in zip(offsets, values))


class _SeenLayouts:
    """The region layouts landed so far, the most recent ``size`` of them.
    The split compiles once per layout, and only for a layout seen before,
    so a stream of batches whose layouts never repeat compiles none."""

    def __init__(self, size: int):
        self.size = size
        self._seen: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def again(self, layout) -> bool:
        """Record ``layout``; whether it was seen before."""
        with self._lock:
            if layout in self._seen:
                self._seen.move_to_end(layout)
                return True
            self._seen[layout] = None
            if len(self._seen) > self.size:
                self._seen.popitem(last=False)
            return False


# process-wide, as the split's compiled programs are
_SEEN = _SeenLayouts(64)


@functools.partial(jax.jit, static_argnums=1)
def _split(words, layout):
    """Each column cut out of a region's uint32 words: a slice of its
    words, bitcast to its dtype; 1- and 2-byte values come out of each
    word as a row of 4 or 2, flattened and cut to the row count. Called
    with the host's words, its dispatch is their one transfer."""
    cols = []
    for offset, rows, dtype in layout:
        per_word = 4 // dtype.itemsize
        n = -(-rows // per_word)
        cut = lax.bitcast_convert_type(
            lax.slice(words, (offset,), (offset + n,)), dtype)
        cols.append(cut if per_word == 1 else cut.reshape(-1)[:rows])
    return tuple(cols)


def batch_to_device(batch: RecordBatch, mesh: Mesh | None = None,
                    specs: Mapping[str, P] | P | None = None) -> DeviceBatch:
    """Zero-staging path: one transfer of the batch's receive region where
    its columns sit in one (module docstring), else per-column device_put
    with explicit sharding."""
    values = [c.values for c in batch.columns]
    region = _region(values) if mesh is None else None
    if region is not None and not _SEEN.again((region[0].size, region[1])):
        region = None
    names = [field.name for field in batch.schema]
    with spans.span(spans.LAND, rows=batch.num_rows,
                    columns=batch.num_columns,
                    transfers=1 if region is not None else len(values),
                    bytes=sum(v.nbytes for v in values)):
        for col in batch.columns:        # before any transfer, either path
            _check_device_column(col)
        if region is not None:
            arrays = _split(*region)
        elif mesh is not None:
            arrays = [jax.device_put(v, NamedSharding(
                mesh, specs[name] if isinstance(specs, Mapping)
                else (specs or P()))) for name, v in zip(names, values)]
        else:
            arrays = [jax.device_put(v) for v in values]
    return DeviceBatch(dict(zip(names, arrays)), batch.num_rows)


def batch_to_device_packed(batch: RecordBatch, mesh: Mesh | None = None,
                           specs: Mapping[str, P] | P | None = None) -> DeviceBatch:
    """Baseline path: pack → single transfer → on-device slice-out."""
    for col in batch.columns:
        _check_device_column(col)
    wire = serialize.pack(batch)  # host staging copy (the overhead)
    if mesh is not None:
        # the packed buffer is replicated (it cannot be column-sharded —
        # precisely why the baseline composes poorly with sharding)
        dev_wire = jax.device_put(wire, NamedSharding(mesh, P()))
    else:
        dev_wire = jax.device_put(wire)

    # Recover per-buffer extents on host from the header (metadata only).
    hlen = int(np.frombuffer(wire[:8].tobytes(), np.uint64)[0])
    import json
    header = json.loads(wire[8 : 8 + hlen].tobytes().decode("utf-8"))
    pos = 8 + hlen + (-hlen) % 8

    cols: dict[str, jax.Array] = {}
    bufs = header["buffers"]
    bi = 0
    for field, col in zip(batch.schema, batch.columns):
        meta = bufs[bi]  # values buffer for this column
        nbytes = meta["nbytes"]
        dtype = np.dtype(meta["dtype"])
        sliced = jax.lax.dynamic_slice(dev_wire, (pos,), (nbytes,))
        arr = jax.lax.bitcast_convert_type(
            sliced.reshape(-1, dtype.itemsize), jnp.dtype(dtype)).reshape(-1)
        if mesh is not None:
            spec = specs[field.name] if isinstance(specs, Mapping) else (specs or P())
            arr = jax.device_put(arr, NamedSharding(mesh, spec))
        cols[field.name] = arr
        # advance past values/offsets/validity (3 buffers per column)
        for _ in range(3):
            nb = bufs[bi]["nbytes"]
            pos += nb + (-nb) % 8
            bi += 1
    return DeviceBatch(cols, batch.num_rows)


def training_batch_specs(mesh: Mesh, batch_axes: tuple[str, ...] = ("pod", "data")) -> P:
    """Canonical sharding for token batches: rows split over the data axes."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    return P(axes if len(axes) > 1 else (axes[0] if axes else None))
