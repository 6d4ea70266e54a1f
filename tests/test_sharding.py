"""Sharding rules + device transport + HLO analyzers (1-device runtime)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.core import batch_from_arrays, schema
from repro.core.device_transport import batch_to_device, batch_to_device_packed
from repro.models import cache_pspecs, cache_spec, make_rules, param_shapes, param_specs
from repro.utils.hlo import collective_stats, shape_bytes
from repro.utils.hlo_cost import analyze


class FakeMesh:
    """Duck-typed stand-in for a (16,16) production mesh — rule/spec logic
    only consults shape/axis_names/size."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


MESH16 = FakeMesh({"data": 16, "model": 16})


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_rules_divisibility(arch):
    cfg = get_config(arch)
    rules = make_rules(cfg, MESH16)
    msize = 16
    if rules.get("heads"):
        assert cfg.eff_heads % msize == 0       # padded-head TP divisibility
    if rules.get("kv"):
        assert cfg.eff_kv % msize == 0
    if rules.get("head_dim"):
        assert cfg.resolved_head_dim % msize == 0
        assert cfg.eff_heads % msize != 0       # cascade only on fallback
    if rules.get("vocab"):
        assert cfg.padded_vocab % msize == 0
    # every arch must shard attention (directly or via padding) or be
    # attention-free
    assert cfg.attention_free or rules.get("heads") or rules.get("head_dim")
    # GQA grouping stays integral under padding
    if cfg.eff_kv:
        assert cfg.eff_heads % cfg.eff_kv == 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_align(arch):
    """Every sharded dim must divide evenly — the compile-time guarantee."""
    cfg = get_config(arch)
    shapes = param_shapes(cfg)
    specs = param_specs(cfg, shapes, MESH16)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for sh, spec in zip(flat_shapes, flat_specs):
        for dim, axis in zip(sh.shape, tuple(spec) + (None,) * 9):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else axis
            n = 1
            for a in axes:
                n *= MESH16.shape[a]
            assert dim % n == 0, f"{arch}: dim {dim} not divisible by {n}"


@pytest.mark.parametrize("arch", ["deepseek-67b", "gemma-2b", "whisper-small"])
def test_cache_specs_align(arch):
    cfg = get_config(arch)
    cs = cache_spec(cfg, 128, 1024)
    specs = cache_pspecs(cfg, cs, MESH16)
    for sh, spec in zip(jax.tree.leaves(cs),
                        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        for dim, axis in zip(sh.shape, tuple(spec) + (None,) * 9):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else axis
            n = 1
            for a in axes:
                n *= MESH16.shape[a]
            assert dim % n == 0


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint8).reshape(x.shape + (x.dtype.itemsize,))


def test_device_transport_parity(rng):
    """thallus path and packed path land bit-identical column arrays, equal
    bit for bit to the host columns (NaNs and signed zeros included)."""
    sch = schema(("a", "float32"), ("b", "int32"), ("c", "float16"))
    a = rng.standard_normal(256).astype(np.float32)
    a[:3] = [np.nan, -0.0, np.inf]
    batch = batch_from_arrays(sch, [a, rng.integers(0, 9, 256).astype(np.int32),
                                    rng.standard_normal(256).astype(np.float16)])
    th = batch_to_device(batch)
    pk = batch_to_device_packed(batch)
    for col in batch.columns:
        name = col.field.name
        assert th[name].dtype == pk[name].dtype == col.values.dtype
        np.testing.assert_array_equal(_bits(th[name]), _bits(col.values))
        np.testing.assert_array_equal(_bits(pk[name]), _bits(th[name]))


@pytest.mark.parametrize("to_device", [batch_to_device, batch_to_device_packed])
def test_device_transport_refuses_inexact_column(rng, to_device):
    """A float64 column would land narrowed to float32 with x64 off: both
    device paths refuse it by name instead."""
    sch = schema(("ok", "float32"), ("wide", "float64"))
    batch = batch_from_arrays(sch, [rng.standard_normal(64).astype(np.float32),
                                    rng.standard_normal(64)])
    with pytest.raises(ValueError, match="'wide' is float64"):
        to_device(batch)


def test_shape_bytes():
    assert shape_bytes("bf16", "2,3") == 12
    assert shape_bytes("f32", "10") == 40
    assert shape_bytes("pred", "8") == 8


def test_hlo_cost_counts_loop_trips():
    """The whole point of the analyzer: a scanned dot counts x trip_count."""
    def step(w, x):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    w = jnp.zeros((5, 16, 16))
    x = jnp.zeros((4, 16))
    txt = jax.jit(step).lower(w, x).compile().as_text()
    cost = analyze(txt, 1)
    dot_flops = 2 * 4 * 16 * 16
    assert cost.flops >= 5 * dot_flops          # ×5 loop trips
    assert cost.flops < 20 * dot_flops


def test_collective_stats_parser():
    txt = """
  %all-gather.1 = bf16[16,4096]{1,0} all-gather(%p), replica_groups=[16,16]<=[256]
  %all-reduce.2 = f32[8,8]{1,0} all-reduce(%q), replica_groups={{0,1,2,3}}
"""
    stats = collective_stats(txt, 256)
    assert stats.counts == {"all-gather": 1, "all-reduce": 1}
    ag = 15 / 16 * 16 * 4096 * 2
    ar = 2 * 3 / 4 * 64 * 4
    assert abs(stats.wire_bytes["all-gather"] - ag) < 1
    assert abs(stats.wire_bytes["all-reduce"] - ar) < 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_all_cells(arch):
    from repro.launch.dryrun_lib import input_specs
    cfg = get_config(arch)
    for shape in SHAPES.values():
        spec = input_specs(cfg, shape)
        assert "tokens" in spec
        if shape.kind == "decode":
            assert spec["tokens"].shape == (shape.global_batch, 1)
        elif cfg.family == "vlm":
            assert spec["tokens"].shape[1] == shape.seq_len - cfg.vlm.num_patches
        else:
            assert spec["tokens"].shape == (shape.global_batch, shape.seq_len)
