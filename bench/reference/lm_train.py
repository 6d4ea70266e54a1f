"""Plain reference for the training feed: the decoder-only transformer's
loss, its gradient by ``jax.grad`` and AdamW, written out in jax.numpy
from the configuration file alone. No kernels, no sharding, no remat
policy of the program's; imports nothing of the program.

The forward pass follows the published block (pre-norm RMSNorm, GQA with
rotate-half RoPE, SwiGLU, tied read-out) with the scalars the
configuration file states. To fit one chip beside its optimizer state it
runs one sequence at a time and recomputes each layer in the backward pass
(``jax.checkpoint``), which changes where values live and not what is
computed. In float32 it sets the highest matmul precision; the control
runs the same code with bfloat16 weights and activations.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """x: (S, heads, hd); rotate-half convention."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def layer(model: dict, x, p):
    """One decoder block on one sequence, x: (S, D)."""
    d = weights.dims(model)
    eps, groups = model["rms_norm_eps"], d["H"] // d["KV"]
    h = rms_norm(x, p["ln1"], eps)
    q = rope(jnp.einsum("sd,dhk->shk", h, p["attn"]["wq"]), model["rope_theta"])
    k = rope(jnp.einsum("sd,dhk->shk", h, p["attn"]["wk"]), model["rope_theta"])
    v = jnp.einsum("sd,dhk->shk", h, p["attn"]["wv"])
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32)
    s = s * model["attention_multiplier"]
    S = x.shape[0]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("hqk,khd->qhd", a, v)
    x = x + model["residual_multiplier"] * jnp.einsum(
        "qhe,hed->qd", o, p["attn"]["wo"])
    h = rms_norm(x, p["ln2"], eps)
    m = p["mlp"]
    ff = (jax.nn.silu(h @ m["wg"]) * (h @ m["wu"])) @ m["wd"]
    return x + model["residual_multiplier"] * ff


def sequence_nll(model: dict, params, tokens, labels):
    """Summed next-token NLL over one sequence and its count of targets
    (label -1 is no target)."""
    x = params["embed"][tokens] * model["embedding_multiplier"]
    body = jax.checkpoint(lambda x, p: (layer(model, x, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
    logits = (x @ params["embed"].T).astype(jnp.float32)
    logits = logits / model["logits_scaling"]
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               -1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


def loss_and_grads(model: dict, params, tokens, labels):
    """Mean NLL over the batch (B, S) and its gradient, one row at a time."""
    grad_fn = jax.value_and_grad(
        lambda p, t, l: sequence_nll(model, p, t, l), has_aux=True)

    def row(acc, tl):
        (nll, n), g = grad_fn(params, *tl)
        total, count, gsum = acc
        return (total + nll, count + n,
                jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum,
                             g)), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (total, count, gsum), _ = jax.lax.scan(
        row, (jnp.float32(0), jnp.float32(0), zero), (tokens, labels))
    return total / count, jax.tree.map(lambda g: g / count, gsum)


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    lr, warm = opt["learning_rate"], opt["warmup_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(opt["decay_steps"] - warm, 1), 0.0),
               1.0)
    ratio = opt["min_lr_ratio"]
    return lr * (ratio + (1 - ratio) * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw(opt: dict, lr, t, params, grads, m, v):
    """One AdamW step (decoupled weight decay, global-norm clipping) at
    learning rate ``lr`` and step count ``t`` (from 1), in float32; returns
    params, m, v and the per-leaf norms of the gradient as the update took
    it (clipped)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip_norm"] / (gnorm + 1e-9))
    b1, b2 = opt["beta1"], opt["beta2"]
    g = jax.tree.map(lambda x: x.astype(jnp.float32) * clip, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        step_dir = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                          + opt["eps"])
        return (p32 - lr * (step_dir + opt["weight_decay"] * p32)).astype(
            p.dtype)

    norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
    return jax.tree.map(upd, params, m, v), m, v, norms


def flat_names(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): float(x) for path, x in flat}


def initial(model: dict, seed: int, dtype: str):
    """The seed's weights, drawn in float32 and held in ``dtype``."""
    return jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(dtype), weights.make(model, k)))(
        weights.seed_key(seed))


def readings(model: dict, seed: int, steps: list, dtype: str = "float32",
             precision: str = "highest") -> dict:
    """Follow the first ``len(steps)`` training steps from the seed's
    weights; ``steps`` holds each step's (tokens, labels), (B, S) int32.
    Returns each step's loss, the per-leaf norm of the first step's
    gradient as the optimizer took it (clipped), and the per-leaf norm of
    the parameters' change over all the steps."""
    opt = model["train"]["optimizer"]
    with jax.default_matmul_precision(precision):
        grad_fn = jax.jit(lambda p, t, l: loss_and_grads(model, p, t, l))
        step_fn = jax.jit(lambda lr, t, p, g, m, v: adamw(opt, lr, t, p, g,
                                                          m, v),
                          donate_argnums=(2, 3, 4, 5))
        params = initial(model, seed, dtype)
        m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        losses, first_grad = [], None
        for i, (tokens, labels) in enumerate(steps):
            loss, grads = grad_fn(params, jnp.asarray(tokens),
                                  jnp.asarray(labels))
            params, m, v, norms = step_fn(
                jnp.float32(lr_at(opt, i)), jnp.float32(i + 1), params,
                grads, m, v)
            losses.append(float(loss))
            if i == 0:
                first_grad = flat_names(norms)
        del m, v
        change = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(
                (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)),
            a, b))(params, initial(model, seed, dtype))
        return {"loss": losses, "grad": first_grad,
                "change": flat_names(change)}
