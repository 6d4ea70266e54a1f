"""Zamba2-style hybrid: Mamba2 backbone + a shared attention block.

The backbone is ``L`` mamba2 layers; after every ``shared_every`` of them a
*shared* transformer block runs on ``concat(hidden, original_embedding)``
(width 2·D) and projects back to D. The block's weights are shared across
invocations (one set of params), but each invocation keeps its own KV cache
(caches depend on activations). Zamba2's per-invocation LoRA deltas are
omitted — noted in DESIGN.md §8.

Structure for scan-ability: layers are grouped as ``G = L // every`` groups
of ``every`` mamba layers each followed by one shared-block invocation, plus
``L % every`` trailing mamba layers.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import (apply_rope, chunked_attention, decode_attention,
                     embedding_init, gated_mlp, rms_norm, rms_norm_weight)
from .mamba2 import (init_mamba_layer_params, mamba_block, mamba_decode_block,
                     ssm_dims)
from .sharding import constrain

Params = dict[str, Any]


def hybrid_structure(cfg: ArchConfig) -> tuple[int, int, int]:
    every = cfg.hybrid.shared_every
    groups = cfg.num_layers // every
    tail = cfg.num_layers % every
    return groups, every, tail


def init_hybrid_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV, F = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ks = iter(jax.random.split(k3, 12))
    s2d = 1.0 / math.sqrt(2 * D)
    shared = {
        "attn": {
            "wq": jax.random.normal(next(ks), (2 * D, H, hd), dtype) * s2d,
            "wk": jax.random.normal(next(ks), (2 * D, KV, hd), dtype) * s2d,
            "wv": jax.random.normal(next(ks), (2 * D, KV, hd), dtype) * s2d,
            "wo": jax.random.normal(next(ks), (H, hd, 2 * D), dtype)
                  * (1.0 / math.sqrt(H * hd)),
        },
        "mlp": {
            "wg": jax.random.normal(next(ks), (2 * D, F), dtype) * s2d,
            "wu": jax.random.normal(next(ks), (2 * D, F), dtype) * s2d,
            "wd": jax.random.normal(next(ks), (F, 2 * D), dtype)
                  * (1.0 / math.sqrt(F)),
        },
        "ln1": rms_norm_weight((2 * D,), dtype),
        "ln2": rms_norm_weight((2 * D,), dtype),
        "down": jax.random.normal(next(ks), (2 * D, D), dtype) * s2d,
    }
    params: Params = {
        "embed": embedding_init(k1, cfg.padded_vocab, D, cfg.tie_embeddings,
                                dtype),
        "mamba_layers": init_mamba_layer_params(cfg, k2, cfg.num_layers, dtype),
        "shared": shared,
        "final_norm": rms_norm_weight((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k4, (D, cfg.padded_vocab), dtype)
                             * (1.0 / math.sqrt(D)))
    return params


def _shared_qkv(cfg: ArchConfig, p: Params, h2: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", h2, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h2, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h2, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", "head_dim"))
    k = constrain(k, ("batch", None, "kv", "head_dim"))
    v = constrain(v, ("batch", None, "kv", "head_dim"))
    return q, k, v


def shared_block(cfg: ArchConfig, p: Params, x: jax.Array, x0: jax.Array,
                 positions: jax.Array, collect_cache: bool = False):
    """x, x0: (B,S,D). Returns delta (B,S,D) (+ (k, v) cache)."""
    h2 = jnp.concatenate([x, x0], axis=-1)                 # (B,S,2D)
    h = rms_norm(h2, p["ln1"], cfg.norm_eps)
    q, k, v = _shared_qkv(cfg, p["attn"], h, positions)
    attn = chunked_attention(q, k, v, causal=True, q_positions=positions,
                             k_positions=positions)
    attn = jnp.einsum("bshk,hkd->bsd", attn, p["attn"]["wo"])
    h2 = h2 + attn
    h = rms_norm(h2, p["ln2"], cfg.norm_eps)
    h2 = h2 + gated_mlp(h, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"],
                        cfg.activation)
    delta = jnp.einsum("bsd,de->bse", h2, p["down"])
    if collect_cache:
        return delta, (k, v)
    return delta


def _mamba_stack(cfg: ArchConfig, layers: Params, x: jax.Array, remat: str,
                 collect_cache: bool = False):
    from .transformer import _maybe_remat

    def body(carry, layer_p):
        h = rms_norm(carry, layer_p["ln"], cfg.norm_eps)
        if collect_cache:
            out, (state, conv) = mamba_block(cfg, layer_p, h, return_cache=True)
            new = constrain(carry + out, ("batch", None, "residual"))
            return new, (state, conv)
        out = mamba_block(cfg, layer_p, h)
        new = constrain(carry + out, ("batch", None, "residual"))
        return new, None

    body = _maybe_remat(body, remat)
    return jax.lax.scan(body, x, layers)


def _split_groups(cfg: ArchConfig, layers: Params):
    groups, every, tail = hybrid_structure(cfg)
    head = jax.tree.map(lambda a: a[: groups * every].reshape(
        (groups, every) + a.shape[1:]), layers)
    tail_p = jax.tree.map(lambda a: a[groups * every :], layers) if tail else None
    return head, tail_p


def hybrid_forward(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
                   remat: str = "full", collect_cache: bool = False):
    from .transformer import embed_tokens, logits_fn

    x0 = embed_tokens(cfg, params, tokens)
    B, S, _ = x0.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    groups, every, tail = hybrid_structure(cfg)
    head, tail_p = _split_groups(cfg, params["mamba_layers"])

    caches = {"state": [], "conv": [], "k": [], "v": []}
    x = x0
    # scan over groups would close over per-group caches awkwardly; groups is
    # small (6 for zamba2) so a python loop is fine — the *inner* stacks scan.
    for g in range(groups):
        grp = jax.tree.map(lambda a, g=g: a[g], head)
        x, mc = _mamba_stack(cfg, grp, x, remat, collect_cache)
        if collect_cache:
            caches["state"].append(mc[0])
            caches["conv"].append(mc[1])
            delta, (k, v) = shared_block(cfg, params["shared"], x, x0,
                                         positions, collect_cache=True)
            caches["k"].append(k)
            caches["v"].append(v)
        else:
            delta = shared_block(cfg, params["shared"], x, x0, positions)
        x = constrain(x + delta, ("batch", None, "residual"))
    if tail_p is not None:
        x, mc = _mamba_stack(cfg, tail_p, x, remat, collect_cache)
        if collect_cache:
            caches["state"].append(mc[0])
            caches["conv"].append(mc[1])
    logits = logits_fn(cfg, params, x)
    if not collect_cache:
        return logits
    cache = {
        "state": jnp.concatenate(caches["state"], axis=0),
        "conv": jnp.concatenate(caches["conv"], axis=0),
        "k": jnp.stack(caches["k"], axis=0),     # (G, B, S, KV, hd)
        "v": jnp.stack(caches["v"], axis=0),
    }
    return logits, cache


def hybrid_cache_spec(cfg: ArchConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    d_inner, H, P, N, conv_ch = ssm_dims(cfg)
    groups, every, tail = hybrid_structure(cfg)
    L, W, hd = cfg.num_layers, cfg.ssm.conv_width, cfg.resolved_head_dim
    return {
        "state": jax.ShapeDtypeStruct((L, batch, H, P, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct((L, batch, W - 1, conv_ch), dtype),
        "k": jax.ShapeDtypeStruct((groups, batch, max_len, cfg.num_kv_heads, hd),
                                  dtype),
        "v": jax.ShapeDtypeStruct((groups, batch, max_len, cfg.num_kv_heads, hd),
                                  dtype),
    }


def init_hybrid_cache(cfg: ArchConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        hybrid_cache_spec(cfg, batch, max_len, dtype))


def hybrid_decode(cfg: ArchConfig, params: Params, cache: Params,
                  tokens: jax.Array, position: jax.Array):
    from .transformer import embed_tokens, logits_fn

    x0 = embed_tokens(cfg, params, tokens)
    B = x0.shape[0]
    S_max = cache["k"].shape[2]
    pos2d = jnp.broadcast_to(position[None, None], (B, 1)).astype(jnp.int32)
    pos_b = jnp.broadcast_to(position[None], (B,)).astype(jnp.int32)
    k_positions = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32)[None],
                                   (B, S_max))
    groups, every, tail = hybrid_structure(cfg)

    def mamba_step(x, layer_p, state, conv):
        h = rms_norm(x, layer_p["ln"], cfg.norm_eps)
        out, state, conv = mamba_decode_block(cfg, layer_p, h, state, conv)
        return x + out, state, conv

    new_states, new_convs, new_ks, new_vs = [], [], [], []
    x = x0
    li = 0
    for g in range(groups):
        for i in range(every):
            layer_p = jax.tree.map(lambda a, li=li: a[li], params["mamba_layers"])
            x, st, cv = mamba_step(x, layer_p,
                                   cache["state"][li], cache["conv"][li])
            new_states.append(st)
            new_convs.append(cv)
            li += 1
        # shared block invocation g
        p = params["shared"]
        h2 = jnp.concatenate([x, x0], axis=-1)
        h = rms_norm(h2, p["ln1"], cfg.norm_eps)
        q, k_new, v_new = _shared_qkv(cfg, p["attn"], h, pos2d)
        k_l = jax.lax.dynamic_update_slice_in_dim(
            cache["k"][g], k_new.astype(cache["k"].dtype), position, axis=1)
        v_l = jax.lax.dynamic_update_slice_in_dim(
            cache["v"][g], v_new.astype(cache["v"].dtype), position, axis=1)
        new_ks.append(k_l)
        new_vs.append(v_l)
        attn = decode_attention(q, k_l, v_l, position=pos_b,
                                k_positions=k_positions)
        h2 = h2 + jnp.einsum("bshk,hkd->bsd", attn, p["attn"]["wo"])
        h = rms_norm(h2, p["ln2"], cfg.norm_eps)
        h2 = h2 + gated_mlp(h, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"],
                            cfg.activation)
        x = x + jnp.einsum("bsd,de->bse", h2, p["down"])
    for i in range(tail):
        layer_p = jax.tree.map(lambda a, li=li: a[li], params["mamba_layers"])
        x, st, cv = mamba_step(x, layer_p, cache["state"][li], cache["conv"][li])
        new_states.append(st)
        new_convs.append(cv)
        li += 1
    logits = logits_fn(cfg, params, x)
    new_cache = {
        "state": jnp.stack(new_states, axis=0),
        "conv": jnp.stack(new_convs, axis=0),
        "k": jnp.stack(new_ks, axis=0),
        "v": jnp.stack(new_vs, axis=0),
    }
    return logits, new_cache
