"""Transformer block layers shared by all architectures.

Each ``init_*`` returns a Box-tree (see ``modules``); each ``apply_*``
consumes the *value-only* tree (after ``modules.split``).  Blocks are
polymorphic over execution mode:

  * ``train``   — full-sequence causal forward, no cache.
  * ``prefill`` — full-sequence forward that also emits the KV cache laid
                  out into a fixed ``cache_len`` buffer.
  * ``decode``  — single-token forward reading/updating the cache.

The KV cache for a layer is ``(k, v)`` of shape (B, cache_len, Hkv, hd); a
sliding-window layer uses a rolling buffer of size ``window``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (apply_rope, chunked_attention, decode_attention,
                        dense_attention)
from .modules import (dense_init, gelu, ones_init, rms_norm, swiglu,
                      zeros_init)
from .moe import init_moe, moe_ffn


class KVCache(NamedTuple):
    k: jnp.ndarray   # (B, S_cache, Hkv, hd)
    v: jnp.ndarray


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def init_attention(key, cfg, dtype=jnp.float32, cross: bool = False,
                   d_in: Optional[int] = None):
    """QKV/O projections in *flattened* (d, H·hd) layout.

    H·hd is divisible by the 16-way TP degree for every assigned arch even
    when H itself is not (llava 56H, qwen1.5 20H, arctic 56H) — jit input
    shardings require exact divisibility; the per-head structure only
    appears on activations, where uneven GSPMD sharding is permitted.
    ``d_in`` is the width q, k and v read (zamba2: 2·d); the output is d.
    """
    d, Hq, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    di = d_in or d
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (di, Hq * hd), ("embed", "qkv"), dtype=dtype),
        "wk": dense_init(ks[1], (di, Hkv * hd), ("embed", "kv"), dtype=dtype),
        "wv": dense_init(ks[2], (di, Hkv * hd), ("embed", "kv"), dtype=dtype),
        "wo": dense_init(ks[3], (Hq * hd, d), ("qkv", "embed"),
                         scale=1.0 / (d ** 0.5 * (2 * max(cfg.num_layers, 1)) ** 0.5),
                         dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((Hq * hd,), ("qkv",), dtype)
        p["bk"] = zeros_init((Hkv * hd,), ("kv",), dtype)
        p["bv"] = zeros_init((Hkv * hd,), ("kv",), dtype)
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), ("null",), dtype)
        p["k_norm"] = ones_init((hd,), ("null",), dtype)
    return p


def _project_qkv(p, cfg, x, kv_x, positions, *, use_rope: bool):
    B, S = x.shape[:2]
    Sk = kv_x.shape[1]
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, Hq, hd)
    k = k.reshape(B, Sk, Hkv, hd)
    v = v.reshape(B, Sk, Hkv, hd)
    if "q_norm" in p:  # qwen3 qk-norm (per-head RMS)
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope and cfg.rope != "none":
        kv_positions = positions if kv_x is x else \
            jnp.broadcast_to(jnp.arange(kv_x.shape[1])[None], kv_x.shape[:2])
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope)
        k = apply_rope(k, kv_positions, cfg.rope_theta, cfg.rope)
    return q, k, v


def apply_attention(p, cfg, pcfg, x, *, positions, mode: str = "train",
                    cache: Optional[KVCache] = None, cache_index=None,
                    cache_len: Optional[int] = None, kv_x=None,
                    causal: bool = True, window: int = 0,
                    constrain=lambda t, kind="residual": t, cache_slot=None,
                    scale: Optional[float] = None,
                    ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Unified attention. Returns (out, new_cache).

    ``cache_slot`` (a possibly traced index) names this call's entry in a
    cache stacked on a leading axis: decode writes the new K/V into that
    entry in place and attends over it, prefill writes its cache there.
    ``scale`` is the softmax scale (None: head_dim ** -0.5)."""
    with jax.named_scope("attention"):
        B, S, d = x.shape
        cross = kv_x is not None
        src = kv_x if cross else x
        new_cache = cache

        if mode == "decode" and cross:
            # cross-attention at decode reads the static (precomputed) cache
            q = x @ p["wq"]
            if "bq" in p:
                q = q + p["bq"]
            q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
            if "q_norm" in p:
                q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            out = decode_attention(q, cache.k, cache.v,
                                   jnp.full((B,), cache.k.shape[1], jnp.int32))
            return out.reshape(B, S, -1) @ p["wo"], cache

        q, k, v = _project_qkv(p, cfg, x, src, positions, use_rope=not cross)
        q = constrain(q, "q_heads")
        k = constrain(k, "kv_heads")
        v = constrain(v, "kv_heads")

        if mode == "decode":
            # write new K/V at cache_index (rolling slot for SWA buffers)
            S_cache = cache.k.shape[-3]
            write_pos = cache_index % S_cache if window else cache_index
            new_cache = KVCache(_write_cache(cache.k, k, write_pos, cache_slot),
                                _write_cache(cache.v, v, write_pos, cache_slot))
            kc, vc = new_cache
            if cache_slot is not None:
                kc, vc = (jax.lax.dynamic_index_in_dim(a, cache_slot,
                                                       keepdims=False)
                          for a in new_cache)
            valid = jnp.minimum(cache_index + 1, S_cache)
            out = decode_attention(q, kc, vc, jnp.broadcast_to(valid, (B,)),
                                   scale=scale)
        else:
            if cross:
                out = chunked_attention(q, k, v, causal=False,
                                        q_chunk=pcfg.attn_q_chunk,
                                        k_chunk=pcfg.attn_k_chunk)
            elif S <= 512:
                out = dense_attention(q, k, v, causal=causal, window=window,
                                      scale=scale)
            else:
                out = chunked_attention(q, k, v, causal=causal, window=window,
                                        q_chunk=pcfg.attn_q_chunk,
                                        k_chunk=pcfg.attn_k_chunk,
                                        scale=scale)
            if mode == "prefill":
                new_cache = _build_cache(k, v,
                                         cache_len=cache_len or k.shape[1],
                                         window=window)
                if cache_slot is not None:
                    new_cache = KVCache(*(
                        jax.lax.dynamic_update_index_in_dim(
                            a, n.astype(a.dtype), cache_slot, 0)
                        for a, n in zip(cache, new_cache)))
        B2, S2 = out.shape[:2]
        return out.reshape(B2, S2, -1) @ p["wo"], new_cache


def _write_cache(buf, kv, pos, slot=None):
    """dynamic_update_slice along seq dim (pos may be traced); into entry
    ``slot`` of a leading stacked axis when one is given."""
    start = (0, pos) + (0,) * (kv.ndim - 2)
    if slot is not None:
        kv, start = kv[None], (slot,) + start
    return jax.lax.dynamic_update_slice(buf, kv.astype(buf.dtype), start)


def _build_cache(k, v, cache_len: int, window: int = 0) -> KVCache:
    """Lay prefill K/V into a fixed-size cache buffer.

    For sliding-window layers the buffer holds only the last ``window``
    positions (rolling semantics start aligned so that position p maps to
    slot p % window)."""
    B, S, H, hd = k.shape
    if window and window < cache_len:
        cache_len = window
    if S >= cache_len:
        # keep the last cache_len positions, aligned to their rolling slots
        start = S - cache_len
        ks, vs = k[:, start:], v[:, start:]
        if window:
            shift = start % cache_len
            ks = jnp.roll(ks, shift, axis=1)
            vs = jnp.roll(vs, shift, axis=1)
        return KVCache(ks, vs)
    pad = cache_len - S
    return KVCache(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                   jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))))


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def init_mlp(key, cfg, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), ("embed", "mlp"), dtype=dtype),
        "w_up": dense_init(ks[1], (d, f), ("embed", "mlp"), dtype=dtype),
        "w_down": dense_init(ks[2], (f, d), ("mlp", "embed"),
                             scale=1.0 / (f ** 0.5 * (2 * max(cfg.num_layers, 1)) ** 0.5),
                             dtype=dtype),
    }


def init_mlp_adapter(key, cfg, dtype=jnp.float32):
    """zamba2: one application's rank-r adapter of the gate and up
    projections, ``in_proj`` (d, r) and ``out_proj`` (2, r, d_ff): its
    gate half and its up half, each sharded as ``w_gate`` and ``w_up``."""
    d, r, f = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    ks = jax.random.split(key, 2)
    return {
        "in_proj": dense_init(ks[0], (d, r), ("embed", "null"), dtype=dtype),
        "out_proj": dense_init(ks[1], (2, r, f), ("null", "null", "mlp"),
                               scale=1.0 / r ** 0.5, dtype=dtype),
    }


def apply_mlp(p, x, adapter=None, act: str = "silu"):
    """Gated MLP, ``act`` on the gate (silu: SwiGLU; gelu: zamba2's).
    ``adapter`` (zamba2) adds its low-rank term to the gate and up
    projections: [g, u] = x·W_gu + (x·A)·B."""
    with jax.named_scope("mlp"):
        gate, up = x @ p["w_gate"], x @ p["w_up"]
        if adapter is not None:
            with jax.named_scope("adapter"):
                low = x @ adapter["in_proj"]
                gate = gate + low @ adapter["out_proj"][0]
                up = up + low @ adapter["out_proj"][1]
        gated = (gelu(gate) * up if act == "gelu" else swiglu(gate, up))
        return gated @ p["w_down"]


# --------------------------------------------------------------------------
# full block (pre-norm residual)
# --------------------------------------------------------------------------

def init_attn_block(key, cfg, dtype=jnp.float32, with_cross: bool = False,
                    ffn: str = "mlp"):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": ones_init((cfg.d_model,), ("embed",), dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "ln2": ones_init((cfg.d_model,), ("embed",), dtype),
    }
    if with_cross:
        p["ln_x"] = ones_init((cfg.d_model,), ("embed",), dtype)
        p["cross"] = init_attention(ks[1], cfg, dtype, cross=True)
    if ffn == "moe":
        p["ffn"] = init_moe(ks[2], cfg, dtype)
    else:
        p["ffn"] = init_mlp(ks[2], cfg, dtype)
    return p


def apply_attn_block(p, cfg, pcfg, x, *, positions, mode="train",
                     cache: Optional[KVCache] = None, cache_index=None,
                     cache_len: Optional[int] = None,
                     cross_cache: Optional[KVCache] = None, enc_out=None,
                     causal=True, constrain=lambda t, kind="residual": t,
                     cache_slot=None):
    """Returns (x, new_cache, new_cross_cache, aux_loss)."""
    window = cfg.sliding_window
    h, new_cache = apply_attention(
        p["attn"], cfg, pcfg, rms_norm(x, p["ln1"], cfg.norm_eps),
        positions=positions, mode=mode, cache=cache, cache_index=cache_index,
        cache_len=cache_len, causal=causal, window=window,
        constrain=constrain, cache_slot=cache_slot)
    x = constrain(x + h)
    new_cross = cross_cache
    if "cross" in p:
        xq = rms_norm(x, p["ln_x"], cfg.norm_eps)
        if mode == "decode":
            # static cross cache, built at prefill
            hx, _ = apply_attention(p["cross"], cfg, pcfg, xq,
                                    positions=positions, mode="decode",
                                    cache=cross_cache, kv_x=x,
                                    constrain=constrain)
        else:
            hx, new_cross = apply_attention(
                p["cross"], cfg, pcfg, xq, positions=positions, mode=mode,
                cache_len=enc_out.shape[1], kv_x=enc_out, causal=False,
                constrain=constrain)
        x = constrain(x + hx)
    aux = jnp.zeros((), jnp.float32)
    y = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.n_experts and "router" in p["ffn"]:
        ff, aux = moe_ffn(p["ffn"], y, cfg, constrain=constrain)
    else:
        ff = apply_mlp(p["ffn"], y)
    x = constrain(x + ff)
    return x, new_cache, new_cross, aux


# --------------------------------------------------------------------------
# zamba2's shared block (Zyphra's published layer)
# --------------------------------------------------------------------------

def init_zamba2_block(key, cfg, dtype=jnp.float32):
    """One of zamba2's shared blocks: a norm over concat(hidden,
    embedding), attention from that 2·d input, a norm, the gated MLP."""
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "ln1": ones_init((2 * d,), ("embed",), dtype),
        "attn": init_attention(ks[0], cfg, dtype, d_in=2 * d),
        "ln2": ones_init((d,), ("embed",), dtype),
        "ffn": init_mlp(ks[1], cfg, dtype),
    }


def apply_zamba2_block(p, adapter, cfg, pcfg, h, x0, *, positions,
                       mode="train", cache: Optional[KVCache] = None,
                       cache_index=None, cache_len: Optional[int] = None,
                       constrain=lambda t, kind="residual": t,
                       cache_slot=None):
    """T = MLP(RMSNorm(Attn(RMSNorm(concat(h, x0))))), no residual inside:
    the caller takes T into the next Mamba2 layer's input.  ``x0`` is the
    embedding output; ``adapter`` is this application's, and the MLP's
    gate is GeLU, as in Zyphra's model.  The softmax
    scale is (head_dim / 2) ** -0.5, as Zyphra's model has it (its heads
    are twice as wide as d / n_heads).  Returns (T, new_cache)."""
    with jax.named_scope("shared_in"):
        y = rms_norm(jnp.concatenate([h, x0], axis=-1), p["ln1"],
                     cfg.norm_eps)
    a, new_cache = apply_attention(
        p["attn"], cfg, pcfg, y, positions=positions, mode=mode, cache=cache,
        cache_index=cache_index, cache_len=cache_len, constrain=constrain,
        cache_slot=cache_slot, scale=(cfg.head_dim / 2) ** -0.5)
    y = rms_norm(a, p["ln2"], cfg.norm_eps)
    return apply_mlp(p["ffn"], y, adapter=adapter, act="gelu"), new_cache
