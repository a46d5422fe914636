"""Pallas blockwise int8 quantize/dequantize (gradient compression path).

Same math as ``parallel.compress`` (its jnp functions are the oracle);
this kernel fuses amax + scale + round per VMEM block so the compressed
collective's quantization never round-trips HBM at fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Rows of blocks per grid step: int8 tiles are (32, 128) on the chip, so a
# step holds 32 blocks (or all of them, when there are fewer).  Each row's
# scale is a (rows, 1) column beside its block.
ROWS = 32


def _q_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                 # (rows, block)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-20) / 127.0           # (rows, 1)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _dq_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(
        x_ref.dtype)


def _rows(nb: int) -> tuple[int, int]:
    """(rows per grid step, padded number of blocks)."""
    rows = min(ROWS, nb)
    return rows, -(-nb // rows) * rows


def quantize(x: jnp.ndarray, block: int = 1024, *, interpret: bool = False):
    """x: (n,) → (q int8 (n,), scales fp32 (ceil(n/block),))."""
    n = x.shape[0]
    nb = -(-n // block)
    rows, nbp = _rows(nb)
    pad = nbp * block - n
    xp = (jnp.pad(x, (0, pad)) if pad else x).reshape(nbp, block)
    q, s = pl.pallas_call(
        _q_kernel,
        grid=(nbp // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, block), jnp.int8),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)],
        interpret=interpret,
    )(xp)
    return q.reshape(-1)[:n], s[:nb, 0]


def dequantize(q: jnp.ndarray, scales: jnp.ndarray, block: int = 1024, *,
               out_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    n = q.shape[0]
    nb = scales.shape[0]
    rows, nbp = _rows(nb)
    pad = nbp * block - n
    qp = (jnp.pad(q, (0, pad)) if pad else q).reshape(nbp, block)
    sp = jnp.pad(scales, (0, nbp - nb)).reshape(nbp, 1)
    x = pl.pallas_call(
        _dq_kernel,
        grid=(nbp // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), out_dtype),
        interpret=interpret,
    )(qp, sp)
    return x.reshape(-1)[:n]
