"""Pallas TPU flash-attention (forward): the kernel behind the
``vmem_fused_attention`` roofline accounting.

Grid: (batch·heads, Sq/BLK_Q). Each step holds one query block in VMEM and
loops over KV blocks with the online-softmax recurrence — scores and p
matrices NEVER touch HBM; per-step HBM traffic is exactly q-block + the
streamed k/v blocks + the output block, which is what the fused memory
model in repro.utils.hlo_cost charges.

Production notes (real-TPU variant): k/v would stream via double-buffered
async copies and the backward recomputes p per block (same schedule our
checkpointed jnp scan uses); this forward is the validated seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import interpret_mode

NEG_INF = -1e30
BLK_Q = 128
BLK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, sk: int,
                  blk_k: int, scale: float):
    q = q_ref[0].astype(jnp.float32) * scale          # (BLK_Q, hd)
    q_block = pl.program_id(1)
    q_pos = q_block * BLK_Q + jax.lax.broadcasted_iota(jnp.int32, (BLK_Q, 1), 0)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        s = q @ k_blk.T                                # (BLK_Q, blk_k) VMEM
        if causal:
            k_pos = i * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc = acc * corr + p @ v_blk
        return m_new, l_new, acc

    m0 = jnp.full((BLK_Q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((BLK_Q, 1), jnp.float32)
    a0 = jnp.zeros((BLK_Q, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, sk // blk_k, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """q: (BH, Sq, hd); k/v: (BH, Sk, hd); heads pre-expanded (GQA handled
    by the ops wrapper). Sq % 128 == 0, Sk % 128 == 0. The whole K/V of one
    head sits in VMEM per query block, which bounds Sk (2048 and 8192
    compile for v5e; 32768 exceeds its VMEM)."""
    return _flash_attention(q, k, v, causal=causal,
                            interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, interpret: bool) -> jax.Array:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_flash_kernel, causal=causal, sk=Sk,
                               blk_k=min(BLK_K, Sk), scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(BH, Sq // BLK_Q),
        in_specs=[
            pl.BlockSpec((1, BLK_Q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLK_Q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
