"""Transport layer: serialization, bulk handles, RPC-vs-Thallus parity, and
the zero-copy properties the paper's numbers rest on."""
import numpy as np
import pytest

from repro.core import (Fabric, FabricConfig, RpcTransport, ThallusTransport,
                        allocate_like, assemble_batch, batch_from_pydict,
                        expose_batch, pack, schema, serialized_size,
                        size_vectors, unpack)
from repro.core.transport import rdma_pull_batch


@pytest.fixture
def batch(rng):
    sch = schema(("a", "int64"), ("b", "float64"), ("s", "utf8"))
    n = 500
    return batch_from_pydict(sch, {
        "a": [int(v) for v in rng.integers(0, 1000, n)],
        "b": [float(v) if i % 11 else None
              for i, v in enumerate(rng.standard_normal(n))],
        "s": [("x" * (i % 13)) if i % 7 else None for i in range(n)],
    })


def test_serialize_roundtrip(batch):
    wire = pack(batch)
    assert wire.nbytes == serialized_size(batch)
    out = unpack(wire)
    assert out.to_pydict() == batch.to_pydict()


def test_deserialize_is_zero_copy(batch):
    """Arrow semantics: unpacked columns are views into the wire buffer."""
    wire = pack(batch)
    out = unpack(wire, zero_copy=True)
    for col in out.columns:
        assert col.values.base is not None


def test_expose_is_zero_copy(batch):
    handle = expose_batch(batch)
    assert handle.num_segments == 3 * batch.num_columns
    # paper layout: 3i/3i+1/3i+2 = values/offsets/validity of column i
    for ci, col in enumerate(batch.columns):
        assert handle.segments[3 * ci] is col.values
        if col.offsets is not None:
            assert handle.segments[3 * ci + 1] is col.offsets
        if col.validity is not None:
            assert handle.segments[3 * ci + 2] is col.validity
    remote = handle.remote_view()
    assert remote.segments is None and remote.descs == handle.descs


def test_size_vectors_match_descs(batch):
    data, offs, nulls = size_vectors(batch)
    handle = expose_batch(batch)
    for ci in range(batch.num_columns):
        assert handle.descs[3 * ci].nbytes == data[ci]
        assert handle.descs[3 * ci + 1].nbytes == offs[ci]
        assert handle.descs[3 * ci + 2].nbytes == nulls[ci]


def test_allocate_like_and_assemble(batch):
    remote = expose_batch(batch)
    local = allocate_like(remote.descs)
    assert [s.nbytes for s in local.segments] == \
           [s.nbytes for s in remote.segments]
    for src, dst in zip(remote.segments, local.segments):
        if src.nbytes:
            dst.view(np.uint8).reshape(-1)[:] = src.view(np.uint8).reshape(-1)
    out = assemble_batch(batch.schema, batch.num_rows, local.segments)
    assert out.to_pydict() == batch.to_pydict()


def test_allocate_like_gives_one_region(batch):
    """Every segment is a view into one buffer at a 64-byte-aligned offset,
    with its exact size and dtype, and no two segments overlap."""
    descs = expose_batch(batch).descs
    local = allocate_like(descs)
    region = local.segments[0].base
    assert isinstance(region, np.ndarray)
    start = region.__array_interface__["data"][0]
    spans = []
    for seg, d in zip(local.segments, descs):
        assert seg.base is region
        assert seg.nbytes == d.nbytes and seg.dtype == np.dtype(d.dtype)
        offset = seg.__array_interface__["data"][0] - start
        assert offset % 64 == 0
        spans.append((offset, offset + seg.nbytes))
    spans.sort()
    assert all(a_end <= b for (_, a_end), (b, _) in zip(spans, spans[1:]))
    assert spans[-1][1] <= region.nbytes


def test_allocate_like_pin_zero_fills(batch):
    local = allocate_like(expose_batch(batch).descs, pin=True)
    assert not local.segments[0].base.any()
    assert all(not s.view(np.uint8).any() for s in local.segments)


def test_pull_assembles_zero_copy_over_the_region(batch):
    """A pull through ``rdma_pull_batch`` lands in the region, and the
    assembled batch's buffers are views of it."""
    got, local, _ = rdma_pull_batch(Fabric(), batch.schema, batch.num_rows,
                                    expose_batch(batch))
    assert got.to_pydict() == batch.to_pydict()
    region = local.segments[0].base
    for ci, col in enumerate(got.columns):
        assert col.values.base is region
        assert np.shares_memory(col.values, local.segments[3 * ci])
        if col.offsets is not None:
            assert np.shares_memory(col.offsets, local.segments[3 * ci + 1])
        if col.validity is not None:
            assert col.validity is local.segments[3 * ci + 2]


def test_transport_parity(batch):
    fabric = Fabric()
    rpc_out, rpc_stats = RpcTransport(fabric).send_batch(batch)
    th_out, th_stats = ThallusTransport(fabric).send_batch(batch)
    assert rpc_out.to_pydict() == th_out.to_pydict() == batch.to_pydict()
    # the defining asymmetry: baseline pays serialization, Thallus does not
    assert rpc_stats.serialize_s > 0
    assert th_stats.serialize_s == 0.0
    assert th_stats.wire.num_segments == 3 * batch.num_columns


def test_thallus_faster_at_scale(rng):
    """Fig-2 direction: for large batches thallus wins; the model's constant
    per-segment costs erode the gain for tiny batches."""
    sch = schema(*[(f"c{i}", "float64") for i in range(8)])
    from repro.core import batch_from_arrays
    big = batch_from_arrays(sch, [rng.standard_normal(200_000) for _ in range(8)])
    fabric = Fabric()
    _, rpc = RpcTransport(fabric).send_batch(big)
    _, th = ThallusTransport(fabric).send_batch(big)
    assert th.total_s < rpc.total_s
    small = batch_from_arrays(sch, [rng.standard_normal(4) for _ in range(8)])
    _, rpc_s = RpcTransport(fabric).send_batch(small)
    _, th_s = ThallusTransport(fabric).send_batch(small)
    gain_big = rpc.total_s / th.total_s
    gain_small = rpc_s.total_s / th_s.total_s
    assert gain_big > gain_small  # the paper's diminishing-gain trend


def test_fabric_counters(batch):
    fabric = Fabric(FabricConfig())
    ThallusTransport(fabric).send_batch(batch)
    assert fabric.rdma_count == 1
    assert fabric.bytes_over_rdma == batch.nbytes
    assert fabric.bytes_over_rpc < 1024  # control plane only
