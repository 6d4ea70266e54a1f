"""Shared neural building blocks: norms, RoPE, gated MLPs, chunked attention.

Everything is functional (params are explicit pytrees) and shape-polymorphic
enough to be used both concrete (smoke tests) and abstract (dry-run lowering
on 512 placeholder devices). Attention is *chunked* with an online-softmax
scan over KV blocks so 32k-token prefill lowers with bounded live memory —
the jnp expression of the flash-attention schedule (the Pallas splash kernel
would slot in here on real hardware; on this CPU container the chunked-jnp
form is what we can validate and cost-analyse).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             zero_centered: bool = False) -> jax.Array:
    """RMSNorm in fp32 (gemma-style ``(1+w)`` when zero_centered)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    x32 = x32 * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    out = x32 * (1.0 + w) if zero_centered else x32 * w
    return out.astype(dtype)


def rms_norm_weight(shape, dtype, zero_centered: bool = False) -> jax.Array:
    """Identity RMSNorm weight: 0 for the ``(1+w)`` form, 1 for ``x*w``."""
    return (jnp.zeros if zero_centered else jnp.ones)(shape, dtype)


def embedding_init(key: jax.Array, vocab: int, d_model: int, tied: bool,
                   dtype) -> jax.Array:
    """Embedding table. Untied, it is only looked up and starts at std 1.
    Tied, it is the readout too: rows at std 1/(2*sqrt(d_model)) give
    logits of std 1/2 on a unit-RMS final norm, so an untrained model's
    loss starts near ln(vocab) + 1/8 (at std 1 the logits have std
    sqrt(d_model) and the loss is off by hundreds of nats)."""
    std = 0.5 / np.sqrt(d_model) if tied else 1.0
    return jax.random.normal(key, (vocab, d_model), dtype) * std


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                   # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------


def _activate(x: jax.Array, kind: str) -> jax.Array:
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(x, approximate=True)
    if kind in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


def gated_mlp(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
              activation: str) -> jax.Array:
    """(B,S,D) -> (B,S,D) with gate/up (D,F) and down (F,D)."""
    gate = _activate(jnp.einsum("bsd,df->bsf", x, wg), activation)
    up = jnp.einsum("bsd,df->bsf", x, wu)
    return jnp.einsum("bsf,fd->bsd", gate * up, wd)


def plain_mlp(x: jax.Array, wi: jax.Array, wd: jax.Array,
              activation: str = "gelu") -> jax.Array:
    h = _activate(jnp.einsum("bsd,df->bsf", x, wi), activation)
    return jnp.einsum("bsf,fd->bsd", h, wd)


# ---------------------------------------------------------------------------
# attention — chunked online-softmax over KV blocks (GQA-native)
# ---------------------------------------------------------------------------


def _kv_chunks(seq: int, target: int) -> int:
    """Largest divisor of ``seq`` that is <= target (static shapes for scan)."""
    target = min(seq, target)
    for c in range(target, 0, -1):
        if seq % c == 0:
            return c
    return seq


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool,
                      q_positions: jax.Array,
                      k_positions: jax.Array,
                      scale: float | None = None,
                      kv_chunk: int = 1024,
                      logit_softcap: float | None = None) -> jax.Array:
    """GQA attention without materializing (Sq, Sk) for the full KV length.

    q: (B, Sq, H, hd) — H query heads
    k, v: (B, Sk, KV, hd) — KV heads; H % KV == 0 (GQA groups = H // KV)
    positions: (B, Sq) / (B, Sk) absolute positions (mask = qpos >= kpos)
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd) * jnp.asarray(scale, q.dtype)

    chunk = _kv_chunks(Sk, kv_chunk)
    n_chunks = Sk // chunk
    kc = k.reshape(B, n_chunks, chunk, KV, hd)
    vc = v.reshape(B, n_chunks, chunk, KV, hd)
    pc = k_positions.reshape(B, n_chunks, chunk)

    def step(carry, inputs):
        # named_scope marks this block as VMEM-fused for the roofline memory
        # model: on TPU it runs as the Pallas flash kernel
        # (kernels/attention), whose score/p tensors never touch HBM.
        with jax.named_scope("vmem_fused_attention"):
            m_prev, l_prev, acc_prev = carry
            k_blk, v_blk, p_blk = inputs  # (B, chunk, KV, hd), (B, chunk)
            s = jnp.einsum("bqkgh,bckh->bkgqc", qg, k_blk,
                           preferred_element_type=jnp.float32)  # (B,KV,G,Sq,c)
            if logit_softcap is not None:
                s = jnp.tanh(s / logit_softcap) * logit_softcap
            if causal:
                mask = (q_positions[:, None, None, :, None]
                        >= p_blk[:, None, None, None, :])
            else:
                mask = p_blk[:, None, None, None, :] >= 0
            s = jnp.where(mask, s, NEG_INF)
            m_blk = jnp.max(s, axis=-1)                       # (B,KV,G,Sq)
            m_new = jnp.maximum(m_prev, m_blk)
            # guard fully-masked rows: keep exp finite
            p = jnp.exp(s - m_new[..., None])
            l_corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * l_corr + jnp.sum(p, axis=-1)
            acc_corr = l_corr[..., None]
            acc_blk = jnp.einsum("bkgqc,bckh->bkgqh", p,
                                 v_blk.astype(jnp.float32))
            acc_new = acc_prev * acc_corr + acc_blk
            return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    # scan over chunk axis: move it to front. The step is checkpointed so
    # the backward pass RECOMPUTES per-chunk scores instead of stacking the
    # (Sq × chunk) p-matrices across chunks — the flash-attention schedule
    # expressed in jnp (on TPU the Pallas splash kernel does this in VMEM).
    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.moveaxis(pc, 1, 0))
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable),
        (m0, l0, a0), xs)
    out = acc / jnp.maximum(l[..., None], 1e-37)
    out = jnp.moveaxis(out, 3, 1)                          # (B,Sq,KV,G,hd)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     position: jax.Array, k_positions: jax.Array,
                     scale: float | None = None,
                     logit_softcap: float | None = None) -> jax.Array:
    """Single-step decode: q (B, 1, H, hd) vs cache (B, S, KV, hd); positions
    beyond ``position`` (per batch, (B,)) are masked out. O(S) per step."""
    B, _, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    # vmem_fused: decode attention reads the KV cache ONCE from HBM; scores
    # and the softmax stay on chip (flash-decoding kernel).
    with jax.named_scope("vmem_fused_attention"):
        qg = q.reshape(B, KV, G, hd) * jnp.asarray(scale, q.dtype)
        s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32)
        if logit_softcap is not None:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        mask = k_positions[:, None, None, :] <= position[:, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
        return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: jax.Array | None = None) -> jax.Array:
    """Token-mean CE. logits (B,S,V) fp32-reduced; labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
