"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant8 import dequantize as p_dq, quantize as p_q
from repro.kernels.reduce_tree import ref_reduce, tree_reduce
from repro.kernels.ssd_scan import ssd_scan
from repro.models.attention import dense_attention
from repro.models.ssm import ssd_reference
from repro.parallel.compress import dequantize as j_dq, quantize as j_q

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("B,S,H,hd", [
    (1, 64, 1, 64), (2, 128, 4, 64), (1, 200, 2, 80), (2, 96, 8, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, dtype, causal):
    q = (jax.random.normal(KEY, (B, S, H, hd)) * 0.5).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd)) * 0.5
         ).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (B, S, H, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **tol(dtype))


@pytest.mark.parametrize("S,chunk", [(64, 16), (100, 32), (96, 96)])
@pytest.mark.parametrize("G", [1, 2])
def test_ssd_scan_sweep(S, chunk, G):
    B, H, hd, N = 2, 4, 16, 8
    x = jax.random.normal(KEY, (B, S, H, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, G, N)) * 0.4
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, G, N)) * 0.4
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-3)


@pytest.mark.parametrize("n,L,block", [(2, 100, 64), (7, 1000, 256),
                                       (16, 4096, 1024), (33, 513, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_reduce_sweep(n, L, block, dtype):
    shards = (jax.random.normal(KEY, (n, L)) * 2).astype(dtype)
    out = tree_reduce(shards, block=block, interpret=True)
    ref = ref_reduce(shards)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("n,block", [(100, 64), (5000, 512), (4096, 1024)])
def test_quant8_matches_jnp(n, block):
    x = jax.random.normal(KEY, (n,)) * 5.0
    q1, s1 = p_q(x, block, interpret=True)
    q2, s2 = j_q(x, block)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    x1 = p_dq(q1, s1, block, interpret=True)
    x2 = j_dq(q2, s2, block)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-6)
    # quantization error bounded by half a quantum per block
    assert float(jnp.max(jnp.abs(x1 - x))) <= float(jnp.max(s1)) * 0.51
