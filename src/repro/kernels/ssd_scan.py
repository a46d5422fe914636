"""Pallas SSD (Mamba2) chunked scan — the intra-chunk quadratic dual plus
the cross-chunk state recurrence, carried in VMEM.

Grid: (batch·heads, n_chunks); the chunk axis iterates sequentially so the
(hd × N) SSM state lives in VMEM scratch across chunks (same carry pattern
as flash attention's online softmax).  Per chunk the kernel computes

    seg[i,j]   = exp(Σ_{k=j+1..i} dt_k·A)          (lower triangular)
    y_intra    = (C·Bᵀ ∘ seg ∘ dt) · x
    y_inter    = C · state_in  ∘ exp(cumsum dt·A)
    state_out  = decay_chunk · state_in + Σ_q B_q (dt_q·decayto_end_q) x_qᵀ

Inputs are pre-arranged to (B·H, S, ·) with B/C repeated per head (the
jnp oracle is ``models.ssm.ssd_chunked`` / ``ssd_reference``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr):
    x = x_ref[0].astype(jnp.float32)          # (Q, hd)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[pl.program_id(0)]               # scalar (per head), SMEM
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    # Per-step scalars arrive as a (Q, 1) column (sublane-major, as the
    # chip tiles a block); the row copies and the cumulative sums are
    # masked reductions over (Q, Q) iotas, so no 1-D vector is relaid.
    Q = dt.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    dA = dt * A                               # (Q, 1) ≤ 0
    dt_row = jnp.sum(jnp.where(row == col, dt, 0.0), axis=0, keepdims=True)
    dA_row = dt_row * A                       # (1, Q)
    cs = jnp.sum(jnp.where(col <= row, dA_row, 0.0), axis=1,
                 keepdims=True)               # (Q, 1) inclusive cumsum
    cs_row = jnp.sum(jnp.where(row <= col, dA, 0.0), axis=0,
                     keepdims=True)           # (1, Q)
    total = jnp.sum(dA_row, axis=1, keepdims=True)   # (1, 1)
    L = jnp.where(row >= col, jnp.exp(cs - cs_row), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * L * dt_row
    y_intra = jax.lax.dot(M, x, preferred_element_type=jnp.float32)

    state_in = state_scr[...]                 # (hd, N)
    y_inter = jax.lax.dot_general(Cm, state_in, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * \
        jnp.exp(cs)                           # decay from chunk start
    y_ref[0, :, :] = (y_intra + y_inter).astype(y_ref.dtype)

    w = dt * jnp.exp(total - cs)              # (Q, 1) dt·decay to chunk end
    contrib = jax.lax.dot_general(
        x * w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (hd, N)
    state_scr[...] = jnp.exp(total) * state_in + contrib


def ssd_scan(x, dt, A, Bmat, Cmat, *, chunk: int = 64,
             interpret: bool = False):
    """x: (B,S,H,hd); dt: (B,S,H); A: (H,); B/C: (B,S,G,N) with H%G==0.
    Returns y: (B,S,H,hd).  On the chip ``chunk`` is a multiple of 8 or
    covers all of S (the block rule for the sequence axis)."""
    Bsz, S, H, hd = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S

    xb = jnp.moveaxis(x, 2, 1).reshape(Bsz * H, S, hd)
    dtb = jnp.moveaxis(dt, 2, 1).reshape(Bsz * H, S, 1)
    Bb = jnp.repeat(Bmat, rep, axis=2)
    Cb = jnp.repeat(Cmat, rep, axis=2)
    Bb = jnp.moveaxis(Bb, 2, 1).reshape(Bsz * H, S, N)
    Cb = jnp.moveaxis(Cb, 2, 1).reshape(Bsz * H, S, N)
    Ab = jnp.tile(A.astype(jnp.float32), Bsz)

    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0)))
        dtb = jnp.pad(dtb, ((0, 0), (0, pad), (0, 0)))
        Bb = jnp.pad(Bb, ((0, 0), (0, pad), (0, 0)))
        Cb = jnp.pad(Cb, ((0, 0), (0, pad), (0, 0)))

    out = pl.pallas_call(
        _kernel,
        grid=(Bsz * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, c: (bh, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz * H, nc * chunk, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interpret,
    )(xb, dtb, Ab, Bb, Cb)
    out = out[:, :S].reshape(Bsz, H, S, hd)
    return jnp.moveaxis(out, 1, 2)
