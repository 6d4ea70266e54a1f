"""Whisper-style encoder-decoder (audio family).

The conv frontend is a STUB per the assignment: ``input_specs()`` provides
precomputed frame embeddings (B, T_enc, D) — what the two conv1d+GELU layers
of Whisper would produce from the log-mel spectrogram. Encoder is
bidirectional, decoder is causal with cross-attention; norms are LayerNorm
(whisper), positional embeddings are learned params, embeddings are tied.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import (chunked_attention, decode_attention, embedding_init,
                     layer_norm, plain_mlp)
from .transformer import mask_padded_vocab
from .sharding import constrain

Params = dict[str, Any]

DEC_POS_MAX = 32768  # covers decode_32k; long_500k skipped (full attention)


def init_encdec_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV, F = cfg.eff_heads, cfg.eff_kv, cfg.d_ff
    Le, Ld = cfg.encdec.num_encoder_layers, cfg.num_layers
    T_enc = cfg.encdec.encoder_seq
    ks = iter(jax.random.split(key, 24))
    s_d = 1.0 / math.sqrt(D)

    def attn(L, kdim=D):
        sk = 1.0 / math.sqrt(kdim)
        return {
            "wq": jax.random.normal(next(ks), (L, D, H, hd), dtype) * s_d,
            "wk": jax.random.normal(next(ks), (L, kdim, KV, hd), dtype) * sk,
            "wv": jax.random.normal(next(ks), (L, kdim, KV, hd), dtype) * sk,
            "wo": jax.random.normal(next(ks), (L, H, hd, D), dtype)
                  * (1.0 / math.sqrt(H * hd)),
        }

    def lnp(L, width=D):
        return {"w": jnp.ones((L, width), dtype), "b": jnp.zeros((L, width), dtype)}

    def mlp(L):
        return {
            "wi": jax.random.normal(next(ks), (L, D, F), dtype) * s_d,
            "wd": jax.random.normal(next(ks), (L, F, D), dtype)
                  * (1.0 / math.sqrt(F)),
        }

    return {
        # the decoder's readout is always the embedding table
        "embed": embedding_init(next(ks), cfg.padded_vocab, D, True, dtype),
        "enc_pos": jax.random.normal(next(ks), (T_enc, D), dtype) * 0.01,
        "dec_pos": jax.random.normal(next(ks), (DEC_POS_MAX, D), dtype) * 0.01,
        "encoder": {"attn": attn(Le), "mlp": mlp(Le),
                    "ln1": lnp(Le), "ln2": lnp(Le)},
        "enc_final_ln": {"w": jnp.ones((D,), dtype), "b": jnp.zeros((D,), dtype)},
        "decoder": {"self_attn": attn(Ld), "cross_attn": attn(Ld),
                    "mlp": mlp(Ld), "ln1": lnp(Ld), "ln2": lnp(Ld),
                    "ln3": lnp(Ld)},
        "dec_final_ln": {"w": jnp.ones((D,), dtype), "b": jnp.zeros((D,), dtype)},
    }


def _mha(cfg, p, xq, xkv, q_positions, k_positions, causal):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    q = constrain(q, ("batch", None, "heads", "head_dim"))
    out = chunked_attention(q, k, v, causal=causal, q_positions=q_positions,
                            k_positions=k_positions)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode(cfg: ArchConfig, params: Params, frames: jax.Array, *,
           remat: str = "full") -> jax.Array:
    """frames: (B, T_enc, D) precomputed (conv-stub output)."""
    from .transformer import _maybe_remat

    B, T, D = frames.shape
    x = frames + params["enc_pos"][None, :T].astype(frames.dtype)
    x = constrain(x, ("batch", None, "residual"))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(carry, layer_p):
        h = layer_norm(carry, layer_p["ln1"]["w"], layer_p["ln1"]["b"])
        x = carry + _mha(cfg, layer_p["attn"], h, h, positions, positions,
                         causal=False)
        h = layer_norm(x, layer_p["ln2"]["w"], layer_p["ln2"]["b"])
        x = x + plain_mlp(h, layer_p["mlp"]["wi"], layer_p["mlp"]["wd"], "gelu")
        return constrain(x, ("batch", None, "residual")), None

    body = _maybe_remat(body, remat)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return layer_norm(x, params["enc_final_ln"]["w"], params["enc_final_ln"]["b"])


def decode_train(cfg: ArchConfig, params: Params, enc_out: jax.Array,
                 tokens: jax.Array, *, remat: str = "full") -> jax.Array:
    """Teacher-forced decoder forward -> logits (B, S, V)."""
    from .transformer import _maybe_remat

    B, S = tokens.shape
    T = enc_out.shape[1]
    x = params["embed"][tokens] + params["dec_pos"][None, :S].astype(
        params["embed"].dtype)
    x = constrain(x, ("batch", None, "residual"))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(carry, layer_p):
        h = layer_norm(carry, layer_p["ln1"]["w"], layer_p["ln1"]["b"])
        x = carry + _mha(cfg, layer_p["self_attn"], h, h, positions, positions,
                         causal=True)
        h = layer_norm(x, layer_p["ln2"]["w"], layer_p["ln2"]["b"])
        x = x + _mha(cfg, layer_p["cross_attn"], h, enc_out, positions,
                     enc_positions, causal=False)
        h = layer_norm(x, layer_p["ln3"]["w"], layer_p["ln3"]["b"])
        x = x + plain_mlp(h, layer_p["mlp"]["wi"], layer_p["mlp"]["wd"], "gelu")
        return constrain(x, ("batch", None, "residual")), None

    body = _maybe_remat(body, remat)
    x, _ = jax.lax.scan(body, x, params["decoder"])
    x = layer_norm(x, params["dec_final_ln"]["w"], params["dec_final_ln"]["b"])
    logits = mask_padded_vocab(cfg, jnp.einsum("bsd,vd->bsv", x, params["embed"]))
    return constrain(logits, ("batch", None, "vocab"))


def encdec_forward(cfg: ArchConfig, params: Params, frames: jax.Array,
                   tokens: jax.Array, *, remat: str = "full") -> jax.Array:
    enc_out = encode(cfg, params, frames, remat=remat)
    return decode_train(cfg, params, enc_out, tokens, remat=remat)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def encdec_cache_spec(cfg: ArchConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    hd, KV, Ld = cfg.resolved_head_dim, cfg.eff_kv, cfg.num_layers
    T = cfg.encdec.encoder_seq
    return {
        "self_k": jax.ShapeDtypeStruct((Ld, batch, max_len, KV, hd), dtype),
        "self_v": jax.ShapeDtypeStruct((Ld, batch, max_len, KV, hd), dtype),
        "cross_k": jax.ShapeDtypeStruct((Ld, batch, T, KV, hd), dtype),
        "cross_v": jax.ShapeDtypeStruct((Ld, batch, T, KV, hd), dtype),
    }


def encdec_prefill(cfg: ArchConfig, params: Params, frames: jax.Array,
                   tokens: jax.Array, *, remat: str = "full"):
    """Encode audio + teacher-forced prompt pass; returns (logits, cache)."""
    enc_out = encode(cfg, params, frames, remat=remat)
    B, S = tokens.shape
    T = enc_out.shape[1]
    x = params["embed"][tokens] + params["dec_pos"][None, :S].astype(
        params["embed"].dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(carry, layer_p):
        x = carry
        h = layer_norm(x, layer_p["ln1"]["w"], layer_p["ln1"]["b"])
        sp = layer_p["self_attn"]
        q = jnp.einsum("bsd,dhk->bshk", h, sp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, sp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, sp["wv"])
        attn = chunked_attention(q, k, v, causal=True, q_positions=positions,
                                 k_positions=positions)
        x = x + jnp.einsum("bshk,hkd->bsd", attn, sp["wo"])
        h = layer_norm(x, layer_p["ln2"]["w"], layer_p["ln2"]["b"])
        cp = layer_p["cross_attn"]
        ck = jnp.einsum("bsd,dhk->bshk", enc_out, cp["wk"])
        cv = jnp.einsum("bsd,dhk->bshk", enc_out, cp["wv"])
        cq = jnp.einsum("bsd,dhk->bshk", h, cp["wq"])
        cattn = chunked_attention(cq, ck, cv, causal=False,
                                  q_positions=positions,
                                  k_positions=enc_positions)
        x = x + jnp.einsum("bshk,hkd->bsd", cattn, cp["wo"])
        h = layer_norm(x, layer_p["ln3"]["w"], layer_p["ln3"]["b"])
        x = x + plain_mlp(h, layer_p["mlp"]["wi"], layer_p["mlp"]["wd"], "gelu")
        return x, (k, v, ck, cv)

    x, (sk, sv, ck, cv) = jax.lax.scan(body, x, params["decoder"])
    x = layer_norm(x, params["dec_final_ln"]["w"], params["dec_final_ln"]["b"])
    logits = mask_padded_vocab(cfg, jnp.einsum("bsd,vd->bsv", x, params["embed"]))
    return logits, {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}


def encdec_decode(cfg: ArchConfig, params: Params, cache: Params,
                  tokens: jax.Array, position: jax.Array):
    """One decoder step against self- and cross-KV caches."""
    B = tokens.shape[0]
    S_max = cache["self_k"].shape[2]
    T = cache["cross_k"].shape[2]
    x = params["embed"][tokens]
    x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], position, 1,
                                         axis=0)[None].astype(x.dtype)
    pos_b = jnp.broadcast_to(position[None], (B,)).astype(jnp.int32)
    k_positions = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32)[None],
                                   (B, S_max))
    c_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    far = jnp.full((B,), T + 1, jnp.int32)  # cross-attn: no causal mask

    def body(carry, inputs):
        x = carry
        layer_p, sk, sv, ck, cv = inputs
        h = layer_norm(x, layer_p["ln1"]["w"], layer_p["ln1"]["b"])
        sp = layer_p["self_attn"]
        q = jnp.einsum("bsd,dhk->bshk", h, sp["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", h, sp["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", h, sp["wv"])
        sk = jax.lax.dynamic_update_slice_in_dim(sk, k_new.astype(sk.dtype),
                                                 position, axis=1)
        sv = jax.lax.dynamic_update_slice_in_dim(sv, v_new.astype(sv.dtype),
                                                 position, axis=1)
        attn = decode_attention(q, sk, sv, position=pos_b,
                                k_positions=k_positions)
        x = x + jnp.einsum("bshk,hkd->bsd", attn, sp["wo"])
        h = layer_norm(x, layer_p["ln2"]["w"], layer_p["ln2"]["b"])
        cp = layer_p["cross_attn"]
        cq = jnp.einsum("bsd,dhk->bshk", h, cp["wq"])
        cattn = decode_attention(cq, ck, cv, position=far,
                                 k_positions=c_positions)
        x = x + jnp.einsum("bshk,hkd->bsd", cattn, cp["wo"])
        h = layer_norm(x, layer_p["ln3"]["w"], layer_p["ln3"]["b"])
        x = x + plain_mlp(h, layer_p["mlp"]["wi"], layer_p["mlp"]["wd"], "gelu")
        return x, (sk, sv)

    x, (new_sk, new_sv) = jax.lax.scan(
        body, x, (params["decoder"], cache["self_k"], cache["self_v"],
                  cache["cross_k"], cache["cross_v"]))
    x = layer_norm(x, params["dec_final_ln"]["w"], params["dec_final_ln"]["b"])
    logits = mask_padded_vocab(cfg, jnp.einsum("bsd,vd->bsv", x, params["embed"]))
    new_cache = dict(cache)
    new_cache["self_k"], new_cache["self_v"] = new_sk, new_sv
    return logits, new_cache
