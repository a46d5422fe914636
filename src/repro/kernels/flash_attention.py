"""Pallas TPU flash attention (target: MXU + VMEM tiling).

Grid: (batch·heads, n_q_blocks, n_kv_blocks) — the last axis iterates
sequentially on TPU, so the online-softmax state (m, l, acc) lives in VMEM
scratch and is carried across kv blocks; @pl.when guards initialize at
kv==0 and finalize at the last visited block.  Causal masking prunes
fully-masked kv blocks at trace time via the index map (no wasted MXU
cycles past the diagonal).

Block shapes default to (128, 128) q×kv tiles with the full head_dim in
the minor dimension — MXU-aligned for hd ∈ {64, 80, 128}.

Validated in interpret mode against ``ref.dense_attention`` over shape and
dtype sweeps (tests/test_kernels.py); the production fallback is the pure
jnp ``models.attention.chunked_attention`` (same math).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, kv_len: int, block_q: int,
            block_k: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0, :, :] = (acc_scr[...] /
                          jnp.maximum(l_scr[...], 1e-30)[:, None]
                          ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (B, S, H, hd) with H equal across q/k/v (repeat GQA first).

    Returns (B, S, H, hd) in q.dtype."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = -(-Sq // block_q), -(-Sk // block_k)

    def to_bh(x, S):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, x.shape[-1])

    qb, kb, vb = to_bh(q, Sq), to_bh(k, Sk), to_bh(v, Sk)
    pad_q, pad_k = nq * block_q - Sq, nk * block_k - Sk
    if pad_q:
        qb = jnp.pad(qb, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kb = jnp.pad(kb, ((0, 0), (0, pad_k), (0, 0)))
        vb = jnp.pad(vb, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               kv_len=Sk, block_q=block_q, block_k=block_k,
                               n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    out = out[:, :Sq].reshape(B, H, Sq, hd)
    return jnp.moveaxis(out, 1, 2)
