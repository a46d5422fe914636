"""Plain float32 zamba2 as the registry builds it: the reference for the
``hybrid`` family.

``num_layers`` Mamba2 layers (``reference.ssm.mamba2``, pre-RMSNorm,
residual); after every ``attn_every`` of them one shared block, the same
weights at every application: pre-RMSNorm causal multi-head attention
with rotate-half RoPE on the whole head, then pre-RMSNorm SwiGLU MLP, each
residual.  Where this departs from Zyphra's published Zamba2 (attention
over concat(x, embedding), two alternating shared blocks with LoRA
adapters) the configuration file lists it; the reference follows the
registry, which is what the cell measures.

Attention is computed in blocks of query rows, so a 4096-token prompt
never holds its whole score matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from reference import ssm
from reference.ssm import ROUNDING  # noqa: F401  (run.py reads it here)
from reference.ssm import F32, einsum, exact, matmul, rms_norm


def rope(x, theta):
    """Rotate-half RoPE over the whole head.  x (b,S,H,hd)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs            # S, hd/2
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, block: int = 512):
    """Softmax attention with a causal mask; q (b,S,H,hd), k and v with
    H or fewer heads (grouped)."""
    b, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    blk = min(block, S)
    pad = (-S) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(b, -1, blk, H, hd).swapaxes(0, 1)

    def one(args):
        i, qi = args
        s = einsum("bqhd,bkhd->bhqk", qi, k) * scale
        rows = i * blk + jnp.arange(blk)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (jnp.arange(qb.shape[0]), qb))
    return out.swapaxes(0, 1).reshape(b, -1, H, hd)[:, :S]


def shared_block(p, x, m, rnd):
    eps = m.get("norm_eps", 1e-5)
    p = jax.tree.map(lambda t: t.astype(F32), p)
    b, S, _ = x.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"], eps)
    theta = m.get("rope_theta", 10000.0)
    q = rope(matmul(h, a["wq"], rnd).reshape(b, S, H, hd), theta)
    k = rope(matmul(h, a["wk"], rnd).reshape(b, S, Hkv, hd), theta)
    v = matmul(h, a["wv"], rnd).reshape(b, S, Hkv, hd)
    o = causal_attention(q, k, v).reshape(b, S, H * hd)
    x = x + matmul(o, a["wo"], rnd)
    f = p["ffn"]
    y = rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(matmul(y, f["w_gate"], rnd)) * matmul(y, f["w_up"], rnd)
    return x + matmul(g, f["w_down"], rnd)


def forward(params, tokens, m, rnd=exact, positions=None, remat=False):
    """Logits (b, len(positions) or S, vocab) in float32: groups of
    ``attn_every`` Mamba2 layers, each followed by the shared block."""
    eps = m.get("norm_eps", 1e-5)
    per = m["attn_every"]
    groups = jax.tree.map(
        lambda t: t.reshape(t.shape[0] // per, per, *t.shape[1:]),
        params["blocks"])

    def layer(h, bp):
        return h + ssm.mamba2(bp["ssm"], rms_norm(h, bp["ln"].astype(F32),
                                                  eps), m, rnd), None

    def group(h, gp):
        h, _ = lax.scan(layer, h, gp)
        return shared_block(params["shared_attn"], h, m, rnd), None

    if remat:
        layer = jax.checkpoint(layer)
        group = jax.checkpoint(group)
    x, _ = lax.scan(group, ssm.embed(params, tokens), groups)
    return ssm.head(params, x, m, rnd, positions)


def loss(params, batch, m, rnd=exact):
    return ssm.loss(params, batch, m, rnd, forward_fn=forward)
