"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp ref."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.pack import (layout_segments, pack_ref, pack_segments,
                                pack_tiles, stage_segments, unpack_segments,
                                packed_nbytes, tiles_for, TILE_BYTES)
from repro.kernels.take import (bitmap_expand_ref, expand_validity,
                                take_column, take_ref)

DTYPES = (np.float32, np.int32, np.int64, np.uint8, np.float16)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", [
    [1], [4096], [4096, 4096], [1, 5000, 17], [0, 100], [8192, 64, 3, 4097],
])
def test_pack_roundtrip_shapes_dtypes(rng, dtype, sizes):
    segs = [(rng.standard_normal(n) * 100).astype(dtype) for n in sizes]
    packed, lens = pack_segments(segs)
    assert packed.dtype == jnp.uint8
    assert packed.size == packed_nbytes(lens)
    outs = unpack_segments(packed, lens)
    for s, o in zip(segs, outs):
        np.testing.assert_array_equal(s.view(np.uint8).reshape(-1), o)


def test_pack_kernel_matches_ref(rng):
    segs = [rng.integers(0, 255, n).astype(np.uint8) for n in (100, 9000, 1)]
    staged, seg_lens = stage_segments(segs)
    seg_ids, tile_ids, _ = layout_segments([int(x) for x in seg_lens])
    got = pack_tiles(jnp.asarray(staged), jnp.asarray(seg_ids),
                     jnp.asarray(tile_ids))
    ref = pack_ref(jnp.asarray(staged), jnp.asarray(seg_ids),
                   jnp.asarray(tile_ids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_tiles_for():
    assert tiles_for(0) == 1
    assert tiles_for(1) == 1
    assert tiles_for(TILE_BYTES) == 1
    assert tiles_for(TILE_BYTES + 1) == 2


@pytest.mark.parametrize("dtype", (np.float32, np.int32, np.float16))
@pytest.mark.parametrize("shape", [(64, 1), (130, 7), (512, 128), (300, 200)])
def test_take_matches_ref(rng, dtype, shape):
    vals = (rng.standard_normal(shape) * 10).astype(dtype)
    idx = rng.integers(0, shape[0], 97).astype(np.int32)
    got = np.asarray(take_column(vals, idx))
    ref = np.asarray(take_ref(jnp.asarray(vals), jnp.asarray(idx)))
    np.testing.assert_array_equal(got, ref)


def test_take_1d(rng):
    vals = rng.integers(-5, 5, 777).astype(np.int64)
    idx = rng.integers(0, 777, 33).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(take_column(vals, idx)), vals[idx])


def test_take_spans_grid_steps_and_lane_tiles(rng):
    """More selected rows than one grid step gathers, from a column two
    128-lane tiles wide."""
    vals = rng.standard_normal((5000, 256)).astype(np.float32)
    idx = rng.integers(0, 5000, 3000).astype(np.int32)
    got = np.asarray(take_column(vals, idx))
    np.testing.assert_array_equal(got, vals[idx])


def test_take_out_of_range_reads_like_ref(rng):
    """Negative and past-the-end indices read what the jnp oracle reads,
    and never leave the column."""
    vals = rng.standard_normal((50, 3)).astype(np.float32)
    idx = np.array([-1, -50, 49, 50, 1000, -1000, 0], np.int32)
    got = np.asarray(take_column(vals, idx))
    ref = np.asarray(take_ref(jnp.asarray(vals), jnp.asarray(idx)))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("n", [1, 8, 100, 1024, 4096, 10000])
def test_bitmap_expand_matches_ref(rng, n):
    mask = rng.integers(0, 2, n).astype(bool)
    bm = np.packbits(mask, bitorder="little")
    got = np.asarray(expand_validity(bm, n))
    ref = np.asarray(bitmap_expand_ref(jnp.asarray(bm), n))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got, mask)


# ---------------------------------------------------------------------------
# flash attention (the kernel behind the vmem_fused_attention accounting)
# ---------------------------------------------------------------------------

from repro.kernels.attention import attention_ref, flash_attention, flash_gqa


@pytest.mark.parametrize("shape", [(2, 128, 128, 64), (1, 256, 256, 32),
                                   (1, 128, 384, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(rng, shape, causal):
    BH, Sq, Sk, hd = shape
    if causal and Sq != Sk:
        pytest.skip("causal requires square")
    q = jnp.asarray(rng.standard_normal((BH, Sq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, Sk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, Sk, hd)), jnp.float32)
    got = np.asarray(flash_attention(q, k, v, causal=causal))
    ref = np.asarray(attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_gqa_matches_model_attention(rng):
    """The kernel and the jnp path the models actually lower must agree —
    this is what licenses the fused-memory roofline accounting."""
    from repro.models.layers import chunked_attention
    B, S, H, KV, hd = 2, 256, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    a = np.asarray(flash_gqa(q, k, v, causal=True))
    b = np.asarray(chunked_attention(q, k, v, causal=True, q_positions=pos,
                                     k_positions=pos, kv_chunk=64))
    np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)
