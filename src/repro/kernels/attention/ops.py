"""jit wrapper: GQA expansion + shape management for the flash kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import flash_attention
from .ref import attention_ref  # noqa: F401 (re-export oracle)


def flash_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) with H % KV == 0.
    Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kx = jnp.repeat(k, G, axis=2)
    vx = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kx.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], hd)
    vf = vx.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], hd)
    out = flash_attention(qf, kf, vf, causal=causal)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
