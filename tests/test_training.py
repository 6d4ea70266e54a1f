"""Training substrate: optimizer math, compression, checkpoint/restart,
loader integration, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Fabric, ThallusServer
from repro.data import ThallusLoader, make_token_table, shift_labels
from repro.engine import Engine
from repro.training import (CheckpointManager, OptimizerConfig, TrainConfig,
                            compress_decompress, compression_wire_bytes,
                            dequantize_int8, global_norm, init_train_state,
                            lr_at, make_train_step, quantize_int8)


def test_lr_schedule():
    cfg = OptimizerConfig(learning_rate=1e-3, warmup_steps=10, decay_steps=100,
                          min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.int32(0))) == 0.0
    assert abs(float(lr_at(cfg, jnp.int32(10))) - 1e-3) < 1e-9
    end = float(lr_at(cfg, jnp.int32(100)))
    assert abs(end - 1e-4) < 1e-8
    mid = float(lr_at(cfg, jnp.int32(55)))
    assert end < mid < 1e-3


def test_adamw_descends_quadratic():
    """AdamW on f(w) = |w|^2 must descend."""
    from repro.training import adamw_update, init_opt_state
    cfg = OptimizerConfig(learning_rate=0.1, warmup_steps=0, decay_steps=1000,
                          weight_decay=0.0, grad_clip_norm=100.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(cfg, params)
    for step in range(50):
        grads = {"w": 2 * params["w"]}
        params, state, metrics = adamw_update(cfg, grads, state, params,
                                              jnp.int32(step))
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_quantization_error_feedback():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 0.01, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x)).max()
    assert err <= float(s) / 2 + 1e-9          # half-ulp bound
    # error feedback: accumulated deq over steps tracks accumulated x
    ef = jnp.zeros_like(x)
    total_deq = jnp.zeros_like(x)
    for _ in range(20):
        deq, ef = compress_decompress(x, ef)
        total_deq = total_deq + deq
    drift = np.abs(np.asarray(total_deq - 20 * x)).max()
    assert drift <= float(s) + 1e-9            # EF keeps drift bounded


def test_compression_wire_savings():
    params = {"a": jnp.zeros((1000,)), "b": jnp.zeros((50, 50))}
    fp32, int8 = compression_wire_bytes(params)
    assert fp32 == 4 * 3500
    assert int8 < fp32 / 3.9


def test_microbatch_equivalence(rng):
    """grad accumulation over k microbatches == single big batch (linearity
    of mean loss in batch partitions)."""
    cfg = get_config("olmoe-1b-7b").reduced()
    B, S = 4, 8
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    batch["labels"] = batch["tokens"]
    opt = OptimizerConfig(learning_rate=1e-3, warmup_steps=0, decay_steps=10)
    s1 = init_train_state(cfg, TrainConfig(optimizer=opt, remat="none"),
                          jax.random.PRNGKey(0))
    s2 = jax.tree.map(lambda x: x, s1)
    out1, m1 = make_train_step(cfg, TrainConfig(optimizer=opt, remat="none"))(s1, batch)
    out2, m2 = make_train_step(cfg, TrainConfig(optimizer=opt, remat="none",
                                                microbatches=2))(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)
    # the first moment after one step is (1 - beta1) x the clipped gradient,
    # linear in it; the updated params are not: Adam's g / (|g| + eps) turns
    # f32 rounding of a gradient near eps into a different step
    for a, b in zip(jax.tree.leaves(out1["opt"]["m"]),
                    jax.tree.leaves(out2["opt"]["m"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-8)


def test_checkpoint_restart_loss_continuity(tmp_path, rng):
    """Kill/restart: the resumed run's next loss equals the uninterrupted
    run's — byte-identical state restore."""
    cfg = get_config("granite-3-2b").reduced()
    tcfg = TrainConfig(optimizer=OptimizerConfig(learning_rate=1e-3,
                                                 warmup_steps=2,
                                                 decay_steps=50),
                       remat="none")
    step_fn = make_train_step(cfg, tcfg)
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    batches = []
    for i in range(6):
        t = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
        batches.append({"tokens": t, "labels": t})
    # uninterrupted
    ref = state
    ref_losses = []
    for b in batches:
        ref, m = step_fn(ref, b)
        ref_losses.append(float(m["loss"]))
    # interrupted at step 3
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    cur = state
    for b in batches[:3]:
        cur, m = step_fn(cur, b)
    mgr.save(int(cur["step"]), cur, cursors={"batch_offset": 3})
    restored, man = mgr.restore_latest(like=cur)
    assert man.cursors["batch_offset"] == 3
    resumed_losses = []
    cur = restored
    for b in batches[3:]:
        cur, m = step_fn(cur, b)
        resumed_losses.append(float(m["loss"]))
    np.testing.assert_allclose(resumed_losses, ref_losses[3:], rtol=1e-6)


def test_checkpoint_gc_and_latest(tmp_path):
    cfg = get_config("granite-3-2b").reduced()
    tcfg = TrainConfig(remat="none")
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        state["step"] = jnp.int32(s)
        mgr.save(s, state)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_loader_end_to_end_and_resume(rng):
    eng = Engine()
    eng.register("/d", make_token_table("tok", 64, 32, 1000, seqs_per_batch=16))
    srv = ThallusServer(eng, Fabric())
    loader = ThallusLoader([srv], "SELECT tokens FROM tok", "/d",
                           seq_len=32, batch_seqs=8)
    all_batches = list(loader)
    assert len(all_batches) == 8
    assert all(b["tokens"].shape == (8, 32) for b in all_batches)
    lbl = all_batches[0]["labels"]
    np.testing.assert_array_equal(lbl[:, :-1], all_batches[0]["tokens"][:, 1:])
    assert (lbl[:, -1] == -1).all()
    # resume from cursor offset 2: skips the first two record batches
    loader2 = ThallusLoader([srv], "SELECT tokens FROM tok", "/d",
                            seq_len=32, batch_seqs=8, start_batch=2)
    rest = list(loader2)
    assert len(rest) == 4
    np.testing.assert_array_equal(rest[0]["tokens"], all_batches[4]["tokens"])


def test_loader_straggler_backup():
    eng = Engine()
    eng.register("/d", make_token_table("tok", 32, 16, 100, seqs_per_batch=16))
    slow = ThallusServer(eng, Fabric())
    eng2 = Engine()
    eng2.register("/d", make_token_table("tok", 32, 16, 100, seqs_per_batch=16))
    fast = ThallusServer(eng2, Fabric())
    loader = ThallusLoader([slow, fast], "SELECT tokens FROM tok", "/d",
                           seq_len=16, batch_seqs=8,
                           straggler_deadline_s=0.0)    # everything straggles
    out = list(loader)
    assert loader.stats.backup_requests > 0
    assert len(out) == 4
    # regression: backups must substitute the SAME batch (replicas share the
    # seed, so a wrong start_batch index would surface as different tokens)
    ref = list(ThallusLoader([slow], "SELECT tokens FROM tok", "/d",
                             seq_len=16, batch_seqs=8))
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(got["tokens"], want["tokens"])


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint saved unsharded restores onto a (1,1) host mesh with
    param specs — the elastic path."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import param_specs
    cfg = get_config("granite-3-2b").reduced()
    tcfg = TrainConfig(remat="none")
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, state)
    mesh = make_host_mesh()
    pspecs = param_specs(cfg, state["params"], mesh)
    from jax.sharding import PartitionSpec as P
    specs = {"params": pspecs, "opt": {k: pspecs for k in state["opt"]},
             "step": P()}
    restored, _ = mgr.restore(7, like=state, mesh=mesh, specs=specs)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
