"""Pallas TPU kernel: FlashAttention-style online-softmax attention.

MXU-aligned tiling: the grid walks (batch*kv_head*q_group, q_block); each
step streams kv blocks through VMEM with fori_loop carrying the running
(max, denom, acc) statistics in fp32. Causal and sliding-window masks prune
whole kv blocks via the loop bounds (work skipped, not masked). Block sizes
default to 128x128 (MXU native); head_dim rides along the minor-most axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _attn_kernel(
    q_ref,  # [1, BQ, Dh]
    k_ref,  # [1, Tk, Dh]
    v_ref,  # [1, Tk, Dh]
    o_ref,  # [1, BQ, Dh]
    *,
    block_q: int,
    block_k: int,
    causal: bool,
    window: int | None,
    q_offset: int,
    sm_scale: float,
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [BQ, Dh]
    tk = k_ref.shape[1]
    q_start = qi * block_q + q_offset  # absolute position of first q row

    # kv block range this q block can see
    if causal:
        hi = jnp.minimum(
            pl.cdiv(q_start + block_q, block_k), pl.cdiv(tk, block_k)
        )
    else:
        hi = pl.cdiv(tk, block_k)
    if window is not None:
        lo = jnp.maximum((q_start - window + 1) // block_k, 0)
    else:
        lo = 0

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        kv_rows = pl.ds(ki * block_k, block_k)
        k_blk = k_ref[0, kv_rows, :].astype(jnp.float32)
        v_blk = v_ref[0, kv_rows, :].astype(jnp.float32)
        s = q @ k_blk.T  # [BQ, BK]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < tk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v_blk
        return m_cur, l_cur, acc

    dh = q_ref.shape[-1]
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_k", "interpret"
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [BH, Tq, Dh]  (batch*heads flattened; Tq % block_q == 0)
    k: jnp.ndarray,  # [BH, Tk, Dh]
    v: jnp.ndarray,  # [BH, Tk, Dh]
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, tq, dh = q.shape
    tk = k.shape[1]
    assert tq % block_q == 0, (tq, block_q)
    sm_scale = dh**-0.5
    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        window=window,
        q_offset=q_offset,
        sm_scale=sm_scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
