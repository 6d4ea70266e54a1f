"""Compile rehearsals: every Pallas kernel of the main path, lowered by
Mosaic and compiled for a described (not attached) TPU v5e at the sizes
``chip_smoke.py``'s kernels phase runs, and the landing path's region
split at the widest batch of the TPC-H projection. Nothing executes; what
the chip's compiler would refuse (unaligned blocks, layouts, VMEM) fails
here.

The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads
the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_transport import _split
from repro.kernels.attention.attention import _flash_attention
from repro.kernels.pack.pack import _pack_tiles, _unpack_tiles
from repro.kernels.take.take import _bitmap_expand, _take_rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


# one scan batch serialized: 8 float32 columns of 2^17 rows = 128 tiles each
N_SEG, MAX_TILES = 8, 128


def test_pack_tiles_compiles_for_v5e(one_chip):
    _assert_mosaic(_pack_tiles.lower(
        _shape(one_chip, (N_SEG, MAX_TILES, 32, 128), jnp.uint8),
        _shape(one_chip, (N_SEG * MAX_TILES,), jnp.int32),
        _shape(one_chip, (N_SEG * MAX_TILES,), jnp.int32),
        interpret=False))


def test_unpack_tiles_compiles_for_v5e(one_chip):
    _assert_mosaic(_unpack_tiles.lower(
        _shape(one_chip, (N_SEG * MAX_TILES + 1, 32, 128), jnp.uint8),
        _shape(one_chip, (N_SEG * MAX_TILES,), jnp.int32),
        n_seg=N_SEG, max_tiles=MAX_TILES, interpret=False))


@pytest.mark.parametrize("width", [128, 256])
def test_take_rows_compiles_for_v5e(one_chip, width):
    _assert_mosaic(_take_rows.lower(
        _shape(one_chip, ((1 << 20) * 128 // width, width), jnp.float32),
        _shape(one_chip, (1 << 18,), jnp.int32), interpret=False))


def test_bitmap_expand_compiles_for_v5e(one_chip):
    # one validity bit per row of the 2^25-row scan table
    _assert_mosaic(_bitmap_expand.lower(
        _shape(one_chip, ((1 << 25) // 8,), jnp.uint8), interpret=False))


def test_flash_attention_compiles_for_v5e(one_chip):
    # granite-3-2b's 32 heads of 64 at 2048 tokens
    qkv = _shape(one_chip, (32, 2048, 64), jnp.bfloat16)
    _assert_mosaic(_flash_attention.lower(qkv, qkv, qkv, causal=True,
                                          interpret=False))


def test_region_split_compiles_for_v5e(one_chip):
    # all 15 LINEITEM columns of a 131,072-row batch: 11 int32, 4 uint8
    rows, dtypes = 131072, [np.dtype(np.int32)] * 11 + [np.dtype(np.uint8)] * 4
    layout, words = [], 0
    for dtype in dtypes:
        layout.append((words, rows, dtype))
        words += rows * dtype.itemsize // 4
    compiled = _split.lower(_shape(one_chip, (words,), jnp.uint32),
                            tuple(layout)).compile()
    outs = compiled.out_info
    assert [(o.shape, o.dtype) for o in outs] == [((rows,), d) for d in dtypes]
