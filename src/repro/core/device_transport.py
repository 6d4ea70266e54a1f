"""Device-side Thallus: host↔HBM and HBM↔HBM columnar movement.

The TPU-native translation of the paper's two paths:

* **thallus path** (`batch_to_device`): every column buffer goes host→device
  *individually* via ``jax.device_put`` with an explicit ``NamedSharding`` —
  the scatter-gather DMA analogue. No staging buffer ever exists; the batch
  on device is a *pytree* of per-column arrays (logical assembly, like
  Arrow's zero-copy deserialize).
* **rpc path** (`batch_to_device_packed`): serialize into ONE contiguous
  host buffer (full copy), ship that single buffer, then slice columns back
  out *on device* (more copies). This is the baseline whose cost the
  protocol deletes.

Both produce bit-identical column arrays (tests assert it), so the rest of
the stack — the input pipeline feeding ``train_step`` — is transport-
agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
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


def batch_to_device(batch: RecordBatch, mesh: Mesh | None = None,
                    specs: Mapping[str, P] | P | None = None) -> DeviceBatch:
    """Zero-staging path: per-column device_put with explicit sharding."""
    cols: dict[str, jax.Array] = {}
    with spans.span(spans.LAND, rows=batch.num_rows,
                    columns=batch.num_columns,
                    bytes=sum(c.values.nbytes for c in batch.columns)):
        for field, col in zip(batch.schema, batch.columns):
            _check_device_column(col)
            arr = col.values
            if mesh is not None:
                spec = (specs[field.name] if isinstance(specs, Mapping)
                        else (specs or P()))
                cols[field.name] = jax.device_put(arr,
                                                  NamedSharding(mesh, spec))
            else:
                cols[field.name] = jax.device_put(arr)
    return DeviceBatch(cols, batch.num_rows)


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
