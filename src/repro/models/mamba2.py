"""Mamba2 — SSD (state-space duality) blocks, chunked scan + O(1) decode.

The chunked SSD algorithm (Dao & Gu 2024): sequence split into chunks of
``Q``; within a chunk the output is a masked quadratic form (the "attention
duality"), across chunks a small (H, P, N) state is carried by a scan. Decode
is a single-token state update — this is what makes `long_500k` runnable for
the ssm/hybrid archs while full-attention families skip it.

State math runs in fp32 (dt/decay/cumsum paths), matmuls in the param dtype.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, SSMConfig
from .layers import embedding_init, rms_norm, rms_norm_weight
from .sharding import constrain

Params = dict[str, Any]


def ssm_dims(cfg: ArchConfig) -> tuple[int, int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.ngroups * s.state_dim
    return d_inner, nheads, s.head_dim, s.state_dim, conv_ch


def init_mamba_layer_params(cfg: ArchConfig, key: jax.Array, L: int,
                            dtype=jnp.float32) -> Params:
    """Stacked (L, ...) params for L mamba2 blocks."""
    D = cfg.d_model
    s = cfg.ssm
    d_inner, H, P, N, conv_ch = ssm_dims(cfg)
    in_dim = 2 * d_inner + 2 * s.ngroups * N + H
    ks = iter(jax.random.split(key, 8))
    s_d = 1.0 / math.sqrt(D)
    return {
        "in_proj": jax.random.normal(next(ks), (L, D, in_dim), dtype) * s_d,
        "conv_w": jax.random.normal(next(ks), (L, s.conv_width, conv_ch), dtype)
                  * (1.0 / math.sqrt(s.conv_width)),
        "conv_b": jnp.zeros((L, conv_ch), dtype),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)[None], (L, H)).copy()),
        "D": jnp.ones((L, H), jnp.float32),
        "dt_bias": jnp.zeros((L, H), jnp.float32),
        "norm": rms_norm_weight((L, d_inner), dtype),
        "out_proj": jax.random.normal(next(ks), (L, d_inner, D), dtype)
                    * (1.0 / math.sqrt(d_inner)),
        "ln": rms_norm_weight((L, D), dtype),
    }


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over seq: xBC (B,S,C), w (W,C) -> (B,S,C)."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC)
    for i in range(W):
        out = out + pad[:, i : i + xBC.shape[1], :] * w[i]
    return jax.nn.silu(out + b)


def _split_zxbcdt(cfg: ArchConfig, zxbcdt: jax.Array):
    d_inner, H, P, N, _ = ssm_dims(cfg)
    G = cfg.ssm.ngroups
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : 2 * d_inner + 2 * G * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * G * N :]
    return z, xBC, dt


def _split_xbc(cfg: ArchConfig, xBC: jax.Array):
    d_inner, H, P, N, _ = ssm_dims(cfg)
    G = cfg.ssm.ngroups
    x = xBC[..., :d_inner]
    B_ = xBC[..., d_inner : d_inner + G * N]
    C_ = xBC[..., d_inner + G * N :]
    return x, B_, C_


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
                C_: jax.Array, D_skip: jax.Array, chunk: int,
                return_final_state: bool = False):
    """Chunked SSD scan.

    x (B,S,H,P); dt (B,S,H) fp32 post-softplus; A (H,) negative; B_/C_
    (B,S,G,N); D_skip (H,). Returns (B,S,H,P) in x.dtype
    (+ final (B,H,P,N) state when requested — prefill hands it to decode).
    """
    Bb, S, H, P = x.shape
    G = B_.shape[2]
    Q = math.gcd(S, chunk) if S % chunk else chunk
    nc = S // Q
    hpg = H // G

    xf = x.astype(jnp.float32).reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H)
    Bc = B_.astype(jnp.float32).reshape(Bb, nc, Q, G, N := B_.shape[-1])
    Cc = C_.astype(jnp.float32).reshape(Bb, nc, Q, G, N)

    # vmem_fused: the intra-chunk quadratic form (the "attention duality")
    # runs as a fused SSD kernel on TPU — Lmat/CB/scores are VMEM tiles.
    with jax.named_scope("vmem_fused_attention"):
        dA = dtc * A[None, None, None, :]                  # (B,nc,Q,H) <= 0
        dAcum = jnp.cumsum(dA, axis=2)                     # within-chunk
        seg = dAcum[:, :, :, None, :] - dAcum[:, :, None, :, :]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        # mask before exp: above the diagonal seg > 0 and exp overflows,
        # and a masked inf still sends inf * 0 = nan back through the where
        Lmat = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))

        # intra-chunk (duality: masked attention within the chunk)
        CB = jnp.einsum("bclgn,bcsgn->bclsg", Cc, Bc)      # (B,nc,l,s,G)
        CB = jnp.repeat(CB, hpg, axis=-1)                  # g -> h
        scores = CB * Lmat * dtc[:, :, None, :, :]         # (B,nc,l,s,H)
        y_diag = jnp.einsum("bclsh,bcshp->bclhp", scores, xf)

        # chunk-final states
        decay_end = jnp.exp(dAcum[:, :, -1:, :] - dAcum)   # (B,nc,Q,H)
        Bx = jnp.einsum("bcsgn,bcsh,bcshp->bchpn",
                        Bc, decay_end * dtc, xf)           # (B,nc,H,P,N)

    # inter-chunk recurrence over nc (sequential scan, small state)
    chunk_decay = jnp.exp(dAcum[:, :, -1, :])              # (B,nc,H)

    def step(state, inputs):
        dec, bx = inputs                                   # (B,H), (B,H,P,N)
        new = state * dec[..., None, None] + bx
        return new, state                                  # emit state ENTERING chunk

    s0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    final_state, states_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(Bx, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)              # (B,nc,H,P,N)

    # inter-chunk contribution: decay from chunk start then read with C
    decay_in = jnp.exp(dAcum)                              # (B,nc,Q,H)
    Ch = jnp.repeat(Cc, hpg, axis=-2)                      # (B,nc,Q,H,N)
    y_off = jnp.einsum("bclhn,bclh,bchpn->bclhp", Ch, decay_in, states_in)

    y = y_diag + y_off + xf * D_skip[None, None, None, :, None]
    y = y.reshape(Bb, S, H, P).astype(x.dtype)
    if return_final_state:
        return y, final_state
    return y


def mamba_block(cfg: ArchConfig, p: Params, u: jax.Array,
                return_cache: bool = False):
    """One mamba2 block, full sequence. u (B,S,D) -> (B,S,D)
    (+ (state, conv_cache) when return_cache — the prefill path)."""
    s = cfg.ssm
    d_inner, H, P, N, _ = ssm_dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"])
    z, xBC_raw, dt_raw = _split_zxbcdt(cfg, zxbcdt)
    xBC = _causal_conv(xBC_raw, p["conv_w"], p["conv_b"])
    x, B_, C_ = _split_xbc(cfg, xBC)
    x = constrain(x.reshape(*x.shape[:2], H, P), ("batch", None, "ssm_heads", None))
    B_ = B_.reshape(*B_.shape[:2], s.ngroups, N)
    C_ = C_.reshape(*C_.shape[:2], s.ngroups, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    res = ssd_chunked(x, dt, A, B_, C_, p["D"], s.chunk,
                      return_final_state=return_cache)
    y, final_state = res if return_cache else (res, None)
    y = y.reshape(*y.shape[:2], d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm"],
                 cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    if return_cache:
        conv_cache = xBC_raw[:, -(s.conv_width - 1):, :]   # pre-activation taps
        return out, (final_state, conv_cache)
    return out


def mamba_decode_block(cfg: ArchConfig, p: Params, u: jax.Array,
                       state: jax.Array, conv_cache: jax.Array):
    """One block, one token. u (B,1,D); state (B,H,P,N); conv_cache
    (B,W-1,conv_ch). Returns (out (B,1,D), new_state, new_conv_cache)."""
    s = cfg.ssm
    d_inner, H, P, N, conv_ch = ssm_dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"])
    z, xBC, dt_raw = _split_zxbcdt(cfg, zxbcdt)
    # conv over (cache ++ new token)
    window = jnp.concatenate([conv_cache, xBC[:, 0:1, :].astype(conv_cache.dtype)],
                             axis=1)                      # (B, W, C)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32))
    new_conv_cache = window[:, 1:, :]
    x, B_, C_ = _split_xbc(cfg, conv_out[:, None, :].astype(u.dtype))
    x = x.reshape(-1, H, P).astype(jnp.float32)            # (B,H,P)
    B_ = B_.reshape(-1, s.ngroups, N).astype(jnp.float32)
    C_ = C_.reshape(-1, s.ngroups, N).astype(jnp.float32)
    hpg = H // s.ngroups
    Bh = jnp.repeat(B_, hpg, axis=1)                       # (B,H,N)
    Ch = jnp.repeat(C_, hpg, axis=1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A[None])                             # (B,H)
    state = state * dA[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt, x, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + x * p["D"][None, :, None]
    y = y.reshape(-1, 1, d_inner).astype(u.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), state, new_conv_cache


# ---------------------------------------------------------------------------
# full model (family == "ssm")
# ---------------------------------------------------------------------------


def init_mamba_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    params: Params = {
        "embed": embedding_init(k1, cfg.padded_vocab, cfg.d_model,
                                cfg.tie_embeddings, dtype),
        "layers": init_mamba_layer_params(cfg, k2, cfg.num_layers, dtype),
        "final_norm": rms_norm_weight((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k3, (cfg.d_model, cfg.padded_vocab),
                                               dtype)
                             * (1.0 / math.sqrt(cfg.d_model)))
    return params


def mamba_forward(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
                  remat: str = "full") -> jax.Array:
    from .transformer import _maybe_remat, embed_tokens, logits_fn

    x = embed_tokens(cfg, params, tokens)

    def body(carry, layer_p):
        h = rms_norm(carry, layer_p["ln"], cfg.norm_eps)
        out = carry + mamba_block(cfg, layer_p, h)
        out = constrain(out, ("batch", None, "residual"))
        return out, None

    body = _maybe_remat(body, remat)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return logits_fn(cfg, params, x)


def mamba_cache_spec(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    d_inner, H, P, N, conv_ch = ssm_dims(cfg)
    L, W = cfg.num_layers, cfg.ssm.conv_width
    return {
        "state": jax.ShapeDtypeStruct((L, batch, H, P, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct((L, batch, W - 1, conv_ch), dtype),
    }


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    spec = mamba_cache_spec(cfg, batch, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def mamba_prefill(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
                  remat: str = "full"):
    """Process the prompt, returning (logits, decode cache)."""
    from .transformer import _maybe_remat, embed_tokens, logits_fn

    x = embed_tokens(cfg, params, tokens)

    def body(carry, layer_p):
        h = rms_norm(carry, layer_p["ln"], cfg.norm_eps)
        out, (state, conv) = mamba_block(cfg, layer_p, h, return_cache=True)
        new = constrain(carry + out, ("batch", None, "residual"))
        return new, (state, conv)

    body = _maybe_remat(body, remat)
    x, (states, convs) = jax.lax.scan(body, x, params["layers"])
    logits = logits_fn(cfg, params, x)
    return logits, {"state": states, "conv": convs}


def mamba_decode(cfg: ArchConfig, params: Params, cache: Params,
                 tokens: jax.Array, position: jax.Array):
    """One decode step (position unused by the SSM state but kept for API
    parity with attention decode)."""
    from .transformer import embed_tokens, logits_fn

    x = embed_tokens(cfg, params, tokens)

    def body(carry, inputs):
        x = carry
        layer_p, state, conv = inputs
        h = rms_norm(x, layer_p["ln"], cfg.norm_eps)
        out, state, conv = mamba_decode_block(cfg, layer_p, h, state, conv)
        return x + out, (state, conv)

    x, (new_state, new_conv) = jax.lax.scan(
        body, x, (params["layers"], cache["state"], cache["conv"]))
    logits = logits_fn(cfg, params, x)
    return logits, {"state": new_state, "conv": new_conv}
