"""Pallas tree-reduction combiner — the R-µswitch analogue on TPU.

FRED's in-switch reduction sums N incoming streams *during routing*; the
TPU analogue is the on-chip combiner that reduce-scatter/all-reduce
implementations invoke on each arriving shard.  This kernel performs the
pairwise-tree summation of N stacked shards over VMEM-resident blocks with
fp32 accumulation (deterministic reduction order — unlike a naive serial
sum, the pairwise tree keeps error O(log N), which matters at N=512 pods).

ref oracle: ``ref_reduce`` (fp32 pairwise sum in jnp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref, *, n: int):
    x = x_ref[...].astype(jnp.float32)            # (n, block)
    # pairwise tree reduction
    m = n
    while m > 1:
        half = m // 2
        x = x[:half] + x[half:2 * half] if m % 2 == 0 else \
            jnp.concatenate([x[:half] + x[half:2 * half], x[2 * half:]], 0)
        m = half + (m % 2)
    o_ref[...] = x[0].astype(o_ref.dtype)


def tree_reduce(shards: jnp.ndarray, *, block: int = 4096,
                interpret: bool = False) -> jnp.ndarray:
    """shards: (N, L) → (L,) sum with fp32 tree accumulation."""
    n, L = shards.shape
    block = min(block, L)
    nb = -(-L // block)
    pad = nb * block - L
    x = jnp.pad(shards, ((0, 0), (0, pad))) if pad else shards
    out = pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=(nb,),
        in_specs=[pl.BlockSpec((n, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb * block,), shards.dtype),
        interpret=interpret,
    )(x)
    return out[:L]


def ref_reduce(shards: jnp.ndarray) -> jnp.ndarray:
    """Pairwise-tree fp32 oracle."""
    x = shards.astype(jnp.float32)
    m = x.shape[0]
    while m > 1:
        half = m // 2
        head = x[:half] + x[half:2 * half]
        x = head if m % 2 == 0 else jnp.concatenate([head, x[2 * half:]], 0)
        m = x.shape[0]
    return x[0].astype(shards.dtype)
