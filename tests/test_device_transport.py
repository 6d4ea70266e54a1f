"""Landing a pulled batch in HBM (``core.device_transport.batch_to_device``):
a batch pulled into one receive region lands as one transfer and a split
on the device, bit for bit what the host holds and what per-column puts
land; every other batch lands column by column, as before."""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.cluster.mempool import BufferPool
from repro.core import Fabric, batch_from_arrays, batch_from_pydict, expose_batch, schema
from repro.core import device_transport
from repro.core.device_transport import batch_to_device, batch_to_device_packed
from repro.core.recordbatch import Column, RecordBatch, pack_validity
from repro.core.transport import rdma_pull_batch
from repro.obs import spans

DTYPES = ("int32", "float32", "uint8", "int16", "int8", "uint16")


@pytest.fixture(autouse=True)
def fresh_layouts(monkeypatch):
    """Each test starts with no layout seen, whatever ran before it."""
    monkeypatch.setattr(device_transport, "_SEEN",
                        device_transport._SeenLayouts(64))


@pytest.fixture
def splits(monkeypatch):
    """The layouts ``_split`` was called with, in order."""
    calls = []
    real = device_transport._split

    def spy(words, layout):
        calls.append(layout)
        return real(words, layout)

    monkeypatch.setattr(device_transport, "_split", spy)
    return calls


def host_batch(rng, rows, dtypes=DTYPES, nulls=False):
    """Fresh host columns of ``dtypes``: float32 with NaN, -0.0 and inf,
    integers over their whole range; every third row null with ``nulls``."""
    sch = schema(*[(f"c{i}", d) for i, d in enumerate(dtypes)])
    cols = []
    for field in sch:
        dtype = field.value_dtype
        if dtype.kind == "f":
            v = rng.standard_normal(rows).astype(dtype)
            v[:3] = [np.nan, -0.0, np.inf][:rows]
        else:
            info = np.iinfo(dtype)
            v = rng.integers(info.min, info.max, rows, endpoint=True,
                             dtype=dtype)
        validity = (pack_validity(np.arange(rows) % 3 != 0) if nulls
                    else None)
        cols.append(Column(field, v, validity=validity))
    return RecordBatch(sch, tuple(cols))


def pull(batch, pool=None):
    """``batch`` as the Thallus client receives it: exposed, pulled
    one-to-one into a local bulk, assembled as views."""
    got, local, _ = rdma_pull_batch(Fabric(), batch.schema, batch.num_rows,
                                    expose_batch(batch), pool=pool)
    return got


def bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint8).reshape(x.shape + (x.dtype.itemsize,))


def assert_lands_exactly(landed, batch):
    assert landed.num_rows == batch.num_rows
    assert list(landed.columns) == [f.name for f in batch.schema]
    for field, col in zip(batch.schema, batch.columns):
        got = landed[field.name]
        assert got.dtype == col.values.dtype and got.shape == col.values.shape
        np.testing.assert_array_equal(bits(got), bits(col.values))


@pytest.mark.parametrize("nulls", [False, True], ids=["valid", "nulls"])
@pytest.mark.parametrize("rows", [1001, 4099, 3])
def test_region_path_is_bit_exact(rng, splits, rows, nulls):
    host = host_batch(rng, rows, nulls=nulls)
    pulled = pull(host)
    first = batch_to_device(pulled)            # first sighting: per column
    again = batch_to_device(pulled)            # from the second: the region
    assert len(splits) == 1
    per_column = batch_to_device(host)         # fresh arrays: per column
    assert len(splits) == 1
    for landed in (first, again, per_column):
        assert_lands_exactly(landed, host)
    packed = batch_to_device_packed(host)
    for name in again.columns:
        np.testing.assert_array_equal(bits(again[name]), bits(packed[name]))


def test_float16_lands_per_column_bit_exact(rng, splits):
    """The split does not carry float16 (a cut on a TPU quiets NaN
    payloads), so a batch holding one lands column by column, exactly."""
    host = host_batch(rng, 1001, ("float32", "float16", "uint8"))
    f16 = host.columns[1].values
    f16[:4] = np.array([0x7c01, 0xfe42, 0x8000, 0x7c00],
                       np.uint16).view(np.float16)
    pulled = pull(host)
    for _ in range(3):
        assert_lands_exactly(batch_to_device(pulled), host)
    assert splits == []


def read_land_spans(trace_dir) -> list[dict]:
    from jax.profiler import ProfileData

    (xplane,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    found = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            found += [(e.start_ns, dict(e.stats)) for e in line.events
                      if e.name == spans.LAND]
    return [args for _, args in sorted(found, key=lambda f: f[0])]


def test_a_layout_takes_one_transfer_from_its_second_sighting(rng, tmp_path):
    """Under the profiler (on the CPU): the land span's ``transfers`` is
    one per column on a layout's first sighting and 1 from its second;
    ``columns`` counts columns throughout. A batch in a layout of its own
    lands per column again."""
    host = host_batch(rng, 2048, ("int32", "float32", "uint8"))
    pulled = [pull(host) for _ in range(3)]
    other = pull(host_batch(rng, 2047, ("int32", "float32", "uint8")))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        landed = [batch_to_device(b) for b in (*pulled, other)]
        jax.block_until_ready([d.columns for d in landed])
    finally:
        jax.profiler.stop_trace()
    args = read_land_spans(tmp_path)
    assert [a["transfers"] for a in args] == [3, 1, 1, 3]
    assert [a["columns"] for a in args] == [3, 3, 3, 3]
    assert [a["rows"] for a in args] == [2048, 2048, 2048, 2047]
    assert [a["bytes"] for a in args] == [2048 * 9] * 3 + [2047 * 9]
    for d in landed[:3]:
        assert_lands_exactly(d, host)


def test_layouts_seen_once_never_split(rng, splits):
    """Irregular batches, each in a layout of its own, compile no split."""
    for rows in range(1000, 1010):
        host = host_batch(rng, rows, ("int32", "uint8"))
        assert_lands_exactly(batch_to_device(pull(host)), host)
    assert splits == []


def test_the_record_of_layouts_is_bounded():
    seen = device_transport._SeenLayouts(2)
    assert [seen.again(k) for k in "aab"] == [False, True, False]
    assert seen.again("c") is False            # forgets "a", the oldest
    assert [seen.again(k) for k in "bca"] == [True, True, False]


def test_fresh_arrays_land_per_column(rng, splits):
    host = host_batch(rng, 512)
    for _ in range(3):
        assert_lands_exactly(batch_to_device(host), host)
    assert splits == []


def test_a_mesh_lands_per_column(rng, splits):
    host = host_batch(rng, 512, ("int32", "float32"))
    pulled = pull(host)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    for _ in range(3):
        landed = batch_to_device(pulled, mesh, P("data"))
        assert_lands_exactly(landed, host)
        assert landed["c0"].sharding.spec == P("data")
    assert splits == []


def test_pooled_slabs_land_per_column(rng, splits):
    """A pooled pull gives every segment its own slab: per column."""
    host = host_batch(rng, 1024, ("int32", "float32", "uint8"))
    pool = BufferPool()
    for _ in range(3):
        assert_lands_exactly(batch_to_device(pull(host, pool)), host)
    assert splits == []


def test_a_few_rows_of_a_large_region_land_per_column(rng, splits):
    """Rows sliced out of a region-backed batch would ship the whole
    region's span: they land per column."""
    host = host_batch(rng, 65536, ("int32", "float32"))
    pulled = pull(host)
    for _ in range(3):
        assert_lands_exactly(batch_to_device(pulled.slice(8, 100)),
                             host.slice(8, 100))
    assert splits == []


def test_inexact_and_variable_length_columns_are_refused(rng):
    """A float64 column (narrowed with x64 off) and a variable-length one
    are refused by name, from a pulled region as from fresh arrays, on
    the first sighting and after."""
    wide = batch_from_arrays(schema(("ok", "float32"), ("wide", "float64")),
                             [rng.standard_normal(64).astype(np.float32),
                              rng.standard_normal(64)])
    text = batch_from_pydict(schema(("ok", "int32"), ("s", "utf8")),
                             {"ok": list(range(8)), "s": ["abcd"] * 8})
    for batch, match in ((wide, "'wide' is float64"),
                         (text, "'s' is variable-length")):
        for landing in (batch, pull(batch), pull(batch)):
            with pytest.raises(ValueError, match=match):
                batch_to_device(landing)
