"""Attention: GQA with RoPE variants, qk-norm, sliding windows, and a
memory-bounded chunked ("flash-style") implementation in pure jnp.

The chunked implementation is the *reference semantics* for the Pallas
flash kernel in ``repro.kernels.flash_attention``, and what every model
program runs.

Design notes
------------
* All softmax statistics are fp32; matmuls run in the compute dtype (bf16).
* Chunking is a double ``lax.scan``: outer over query blocks, inner over KV
  blocks with running (max, denom) online-softmax state — O(S·chunk) memory
  instead of O(S²), which is what lets ``prefill_32k`` fit HBM.  The inner
  step is checkpointed, so the backward pass keeps that bound too.
* Causal + sliding-window masks are computed from block offsets, and KV
  blocks that are fully masked are *skipped for memory purposes only* (the
  scan still visits them — XLA hoists the constant mask; on TPU the Pallas
  kernel skips them for compute too).
* Decode (q_len == 1) takes a separate path: no materialized S×S scores,
  works on a KV cache whose *sequence* dim may be sharded over the ``model``
  mesh axis — GSPMD turns the masked softmax reductions into tiny
  all-reduces of per-head statistics (flash-decoding style).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mode: str = "default") -> jnp.ndarray:
    """Rotary embedding, rotate-half convention.

    x: (B, S, H, hd); positions: (B, S) absolute positions.
    ``mode``:
      * ``default`` — rotate the full head_dim (llama-style rotate-half:
        pairs are (x[i], x[i+hd/2])).
      * ``2d``      — chatglm/GLM RoPE: only the first half of head_dim is
        rotated; the second half passes through.
      * ``none``    — identity.

    The rotate-half (rather than interleaved-pair) layout is deliberate:
    it lowers to two slices + one concatenate on the *minor* dim, which the
    SPMD partitioner handles without resharding copies.  Interleaved
    stack+reshape forced an involuntary full rematerialization under
    (SP seq × TP heads) sharding.
    """
    if mode == "none":
        return x
    hd = x.shape[-1]
    rot_dim = hd if mode == "default" else hd // 2
    half = rot_dim // 2
    freqs = rope_frequencies(rot_dim, theta)                      # (half,)
    angles = positions.astype(jnp.float32)[..., None] * freqs     # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)          # (B,S,1,half)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1 = x[..., :half]
    x2 = x[..., half:rot_dim]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    if rot_dim == hd:
        return jnp.concatenate([r1, r2], axis=-1)
    return jnp.concatenate([r1, r2, x[..., rot_dim:]], axis=-1)


# --------------------------------------------------------------------------
# chunked flash attention (pure jnp oracle + production fallback)
# --------------------------------------------------------------------------

def _block_mask(q_off, k_off, q_blk, k_blk, causal, window, kv_len):
    """(q_blk, k_blk) additive mask for a q/k block pair at given offsets."""
    qi = q_off + jnp.arange(q_blk)[:, None]
    kj = k_off + jnp.arange(k_blk)[None, :]
    ok = kj < kv_len
    if causal:
        ok &= kj <= qi
    if window:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA → MHA: (B,S,Hkv,hd) → (B,S,Hkv·n_rep,hd).

    Making the repeat explicit (instead of a grouped 6-D einsum) keeps every
    attention tensor at a single clean head dim, which the SPMD partitioner
    shards over ``model`` without the pathological Hkv×group axis splits we
    measured (all-to-alls inside every chunk-scan iteration)."""
    if n_rep == 1:
        return k
    B, S, H, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, H, n_rep, hd)) \
        .reshape(B, S, H * n_rep, hd)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      *, causal: bool = True, window: int = 0,
                      q_chunk: int = 1024, k_chunk: int = 1024,
                      q_offset: int = 0, kv_len: Optional[int] = None,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax blocked attention.

    q: (B, Sq, H, hd);  k/v: (B, Sk, H, hd) — GQA repeat happens *before*
    this call (see ``repeat_kv``).  Returns (B, Sq, H, hd) in q.dtype.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if k.shape[2] != H:
        k = repeat_kv(k, H // k.shape[2])
        v = repeat_kv(v, H // v.shape[2])
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kv_len = Sk if kv_len is None else kv_len

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    nq, nk = -(-Sq // q_chunk), -(-Sk // k_chunk)
    # pad to whole blocks
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * k_chunk - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * k_chunk - Sk), (0, 0), (0, 0)))

    qb = qp.reshape(B, nq, q_chunk, H, hd)
    kb = kp.reshape(B, nk, k_chunk, H, hd)
    vb = vp.reshape(B, nk, k_chunk, H, hd)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk                                    # qblk: (B,qc,H,hd)
        m0 = jnp.full((B, q_chunk, H), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, H), jnp.float32)
        acc0 = jnp.zeros((B, q_chunk, H, hd), jnp.float32)

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, kblk, vblk = kj_blk
            s = jnp.einsum("bqhd,bkhd->bqhk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qi * q_chunk + q_offset, kj * k_chunk,
                               q_chunk, k_chunk, causal, window, kv_len)
            s = s + mask[None, :, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        ks = (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))
        # the backward pass recomputes each block's scores rather than
        # keeping every block's (stacked: O(S²)) as residuals
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, acc0),
                                      ks)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    qs = (jnp.arange(nq), jnp.moveaxis(qb, 1, 0))
    _, outs = jax.lax.scan(q_step, None, qs)                  # (nq,B,qc,H,hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq]


def dense_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_len=None, scale=None):
    """Plain (materialized-scores) attention — used for short sequences and
    as the numerically trivial oracle for the chunked/Pallas versions."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if k.shape[2] != H:
        k = repeat_kv(k, H // k.shape[2])
        v = repeat_kv(v, H // v.shape[2])
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kv_len = Sk if kv_len is None else kv_len
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qi = q_offset + jnp.arange(Sq)[:, None]
    kj = jnp.arange(Sk)[None, :]
    ok = kj < kv_len
    if causal:
        ok &= kj <= qi
    if window:
        ok &= kj > qi - window
    s = s + jnp.where(ok, 0.0, NEG_INF)[None, :, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     scale: Optional[float] = None):
    """Single-token decode attention over a (possibly seq-sharded) KV cache.

    q: (B, 1, Hq, hd); caches: (B, S, Hkv, hd); cache_len: scalar or (B,)
    count of valid cache positions (the new token's K/V must already be
    written at position cache_len-1... i.e. included).

    The masked max/sum reductions over S are partitioner-friendly: when S is
    sharded over the ``model`` axis, XLA emits partial reductions plus an
    all-reduce over (B, H) statistics — the flash-decoding pattern — instead
    of gathering the cache.
    """
    B, _, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hkv, group, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))        # (B,S)
    if window:
        valid &= pos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bkhd->bhgd", (p / denom).astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)
