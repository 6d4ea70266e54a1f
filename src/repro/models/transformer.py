"""Decoder-only transformer (dense / MoE / VLM families).

Layers are stacked along a leading ``L`` axis and driven by ``lax.scan`` so
the lowered HLO is one layer body regardless of depth (compile time and HLO
size stay flat from gemma-2b to deepseek-67b). Remat policy wraps the scan
body. All activations pass through :func:`repro.models.sharding.constrain`
with logical names, so the same code lowers unsharded on one CPU device and
2D-sharded on a 512-chip mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import (apply_rope, chunked_attention, decode_attention,
                     embedding_init, gated_mlp, rms_norm, rms_norm_weight)
from .moe import init_moe_params, moe_ffn
from .sharding import constrain

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_transformer_params(cfg: ArchConfig, key: jax.Array,
                            dtype=jnp.float32) -> Params:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV, L, F, V = (cfg.eff_heads, cfg.eff_kv, cfg.num_layers,
                      cfg.d_ff, cfg.padded_vocab)
    ks = iter(jax.random.split(key, 16))
    s_d = 1.0 / math.sqrt(D)

    attn = {
        "wq": jax.random.normal(next(ks), (L, D, H, hd), dtype) * s_d,
        "wk": jax.random.normal(next(ks), (L, D, KV, hd), dtype) * s_d,
        "wv": jax.random.normal(next(ks), (L, D, KV, hd), dtype) * s_d,
        "wo": jax.random.normal(next(ks), (L, H, hd, D), dtype)
              * (1.0 / math.sqrt(H * hd)),
    }
    if cfg.qk_norm:
        attn["q_norm"] = rms_norm_weight((L, hd), dtype)
        attn["k_norm"] = rms_norm_weight((L, hd), dtype)

    zc = cfg.zero_centered_norm
    layers: Params = {
        "attn": attn,
        "ln1": rms_norm_weight((L, D), dtype, zc),
        "ln2": rms_norm_weight((L, D), dtype, zc),
    }
    if cfg.moe is not None:
        moe_keys = jax.random.split(next(ks), L)
        stacked = jax.vmap(lambda k: init_moe_params(k, D, cfg.moe, dtype))(moe_keys)
        layers["moe"] = stacked
    else:
        layers["mlp"] = {
            "wg": jax.random.normal(next(ks), (L, D, F), dtype) * s_d,
            "wu": jax.random.normal(next(ks), (L, D, F), dtype) * s_d,
            "wd": jax.random.normal(next(ks), (L, F, D), dtype)
                  * (1.0 / math.sqrt(F)),
        }

    params: Params = {
        "embed": embedding_init(next(ks), V, D, cfg.tie_embeddings, dtype),
        "layers": layers,
        "final_norm": rms_norm_weight((D,), dtype, zc),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(next(ks), (D, V), dtype) * s_d
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _qkv(cfg: ArchConfig, p: Params, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", "head_dim"))
    k = constrain(k, ("batch", None, "kv", "head_dim"))
    v = constrain(v, ("batch", None, "kv", "head_dim"))
    return q, k, v


def _attention_block(cfg: ArchConfig, p: Params, x: jax.Array,
                     positions: jax.Array) -> tuple[jax.Array, tuple]:
    q, k, v = _qkv(cfg, p, x, positions)
    out = chunked_attention(q, k, v, causal=True, q_positions=positions,
                            k_positions=positions,
                            logit_softcap=cfg.logit_softcap)
    out = constrain(out, ("batch", None, "heads", "head_dim"))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def _ffn_block(cfg: ArchConfig, layer_p: Params, x: jax.Array) -> jax.Array:
    if cfg.moe is not None:
        return moe_ffn(x, layer_p["moe"], cfg.moe, cfg.activation)
    m = layer_p["mlp"]
    h = gated_mlp(x, m["wg"], m["wu"], m["wd"], cfg.activation)
    return h


def _decoder_layer(cfg: ArchConfig, layer_p: Params, x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, tuple]:
    # Megatron-SP schedule: norm on the sharded residual (fp32 interior
    # stays sharded), gather the bf16 NORM OUTPUT for the block, and pin
    # block outputs back to residual sharding so the heads-contraction psum
    # lowers as a reduce-scatter instead of a full all-reduce.
    # (Gather-before-norm was tried and REFUTED: the gathered bf16 residual
    # becomes a saved activation and X/M both regressed — EXPERIMENTS §Perf.)
    h = rms_norm(x, layer_p["ln1"], cfg.norm_eps, cfg.zero_centered_norm)
    h = constrain(h, ("batch", None, None))            # AG, bf16
    attn_out, kv = _attention_block(cfg, layer_p["attn"], h, positions)
    attn_out = constrain(attn_out, ("batch", None, "residual"))   # RS, bf16
    x = x + attn_out
    h = rms_norm(x, layer_p["ln2"], cfg.norm_eps, cfg.zero_centered_norm)
    h = constrain(h, ("batch", None, None))            # AG, bf16
    ffn = constrain(_ffn_block(cfg, layer_p, h), ("batch", None, "residual"))
    x = x + ffn
    x = constrain(x, ("batch", None, "residual"))
    return x, kv


_REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _maybe_remat(fn, remat: str):
    policy = _REMAT_POLICIES[remat]
    if remat == "none":
        return fn
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# embed / logits
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ArchConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return constrain(x, ("batch", None, "residual"))


def mask_padded_vocab(cfg: ArchConfig, logits: jax.Array) -> jax.Array:
    """Embedding tables are padded to a 256-multiple (see
    ArchConfig.padded_vocab); the padded rows must never win: -inf them."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    valid = jnp.arange(logits.shape[-1]) < cfg.vocab_size
    return jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))


def logits_fn(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.zero_centered_norm)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = mask_padded_vocab(cfg, logits)
    return constrain(logits, ("batch", None, "vocab"))


# ---------------------------------------------------------------------------
# forward (train / prefill) and decode
# ---------------------------------------------------------------------------


def transformer_forward(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
                        extra_embeds: jax.Array | None = None,
                        remat: str = "full",
                        collect_cache: bool = False):
    """Full-sequence forward. Returns logits, and the per-layer (k, v) cache
    stacked (L, B, S, KV, hd) when ``collect_cache`` (prefill)."""
    x = embed_tokens(cfg, params, tokens)
    if extra_embeds is not None:       # VLM: prepend visual tokens
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, layer_p):
        y, kv = _decoder_layer(cfg, layer_p, carry, positions)
        return y, kv if collect_cache else None

    body = _maybe_remat(body, remat)
    x, kvs = jax.lax.scan(body, x, params["layers"])
    logits = logits_fn(cfg, params, x)
    if collect_cache:
        return logits, {"k": kvs[0], "v": kvs[1]}
    return logits


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.eff_kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.eff_kv, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def transformer_decode(cfg: ArchConfig, params: Params, cache: Params,
                       tokens: jax.Array, position: jax.Array):
    """One decode step. tokens (B, 1); position: scalar int32 index of the
    new token (batch-uniform decode — the batcher aligns requests).
    Returns (logits (B, 1, V), updated cache). The cache write is a
    dynamic_update_slice so each step touches one position, keeping the
    decode memory roofline at cache-read + single-slot-write."""
    x = embed_tokens(cfg, params, tokens)
    B = x.shape[0]
    S_max = cache["k"].shape[2]
    pos2d = jnp.broadcast_to(position[None, None], (B, 1)).astype(jnp.int32)
    k_positions = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32)[None],
                                   (B, S_max))
    pos_b = jnp.broadcast_to(position[None], (B,)).astype(jnp.int32)

    def body(carry, layer_p):
        # The FULL cache rides the carry and is updated at (layer, position)
        # in place — XLA aliases while-loop carries, so the cache has single
        # residency (scan-ys stacking would double-buffer ~the whole cache).
        x, kc, vc, li = carry
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps, cfg.zero_centered_norm)
        q, k_new, v_new = _qkv(cfg, layer_p["attn"], h, pos2d)
        kc = jax.lax.dynamic_update_slice(
            kc, k_new.astype(kc.dtype)[None], (li, 0, position, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v_new.astype(vc.dtype)[None], (li, 0, position, 0, 0))
        k_l = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
        out = decode_attention(q, k_l, v_l, position=pos_b,
                               k_positions=k_positions,
                               logit_softcap=cfg.logit_softcap)
        out = jnp.einsum("bshk,hkd->bsd", out, layer_p["attn"]["wo"])
        x = x + out
        h = rms_norm(x, layer_p["ln2"], cfg.norm_eps, cfg.zero_centered_norm)
        x = x + _ffn_block(cfg, layer_p, h)
        return (x, kc, vc, li + 1), None

    (x, k_new, v_new, _), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"], jnp.int32(0)), params["layers"])
    logits = logits_fn(cfg, params, x)
    return logits, {"k": k_new, "v": v_new}
