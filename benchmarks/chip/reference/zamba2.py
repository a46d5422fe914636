"""Plain float32 Zamba2 as Zyphra published it: the reference for the
``zamba2`` family.

Written from the Zamba2 paper (Glorioso et al., arXiv:2411.15242) and the
layer equations of Hugging Face's ``modeling_zamba2.py``.  ``x0`` is the
embedding output; hybrid layer i is the j-th of ``hybrid_layer_ids`` and
takes shared block j mod ``n_shared_blocks``:

    T   = MLP_j(RMSNorm_d(Attn(RMSNorm_2d(concat(h, x0)))))  (no residual)
    h  <- h + Mamba2_i(RMSNorm(h + L_j T))                    (hybrid layer)
    h  <- h + Mamba2_i(RMSNorm(h))                             (other layers)

Attention projects the 2·d input to heads of ``head_dim`` with
rotate-half RoPE (``reference.hybrid.rope``), softmax scale
(head_dim / 2) ** -0.5 as Hugging Face has it, and back to d; it is computed in blocks of query rows, each recomputed in the
backward pass, so that a 4096-token gradient fits beside the weights.  ``MLP_j(y) = W_down(gelu(g) u)``
with [g, u] = y W_gu + (y A_j) B_j and the exact (erf) GeLU; A_j and B_j
are application j's rank-r adapter.  Mamba2's gated RMSNorm before
``out_proj`` normalises each group's d_inner / ngroups channels.

It imports nothing of the program under test and reads the weights laid
out as the program keeps them: W_gu as ``w_gate`` and ``w_up``, B_j as
its gate and up halves (``adapter.out_proj[j, 0]`` and ``[j, 1]``), the
shared blocks and the per-application leaves stacked on a leading axis.

Departures from the published model:
- SSD is computed in chunks of 64 (``reference.ssm.ssd``), not 256; the
  chunk size changes the blocking, not the result.
- No ``time_step_limit`` clamp or ``time_step_floor``: the published
  config sets no limit, and the floor acts only at initialisation.

Assumed: the attention scale (head_dim / 2) ** -0.5 is Hugging Face's; the
catalog cannot confirm it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference import ssm
from reference.hybrid import rope
from reference.ssm import ROUNDING  # noqa: F401  (run.py reads it here)
from reference.ssm import F32, einsum, exact, matmul, rms_norm


def causal_attention(q, k, v, scale, block: int = 512):
    """Softmax attention with a causal mask, in blocks of query rows, each
    recomputed in the backward pass (so its gradient never holds every
    block's scores at once); q (b,S,H,hd), k and v with H or fewer heads
    (grouped)."""
    b, S, H, hd = q.shape
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    blk = min(block, S)
    pad = (-S) % blk
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qb.reshape(b, -1, blk, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = einsum("bqhd,bkhd->bhqk", qi, k) * scale
        rows = i * blk + jnp.arange(blk)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (jnp.arange(qb.shape[0]), qb))
    return out.swapaxes(0, 1).reshape(b, -1, H, hd)[:, :S]


def mamba2(p, u, m, rnd):
    """One Mamba2 mixer with the per-group gated norm.  u (b,S,d)."""
    b, S, d = u.shape
    di = m["ssm_expand"] * d
    P, N, G = m["ssm_headdim"], m["ssm_state"], m.get("ssm_groups", 1)
    H = di // P
    eps = m.get("norm_eps", 1e-5)
    zxbcdt = matmul(u, p["in_proj"], rnd)
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N:]
    K = p["conv_w"].shape[0]
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    x = xBC[..., :di].reshape(b, S, H, P)
    Bm = xBC[..., di:di + G * N].reshape(b, S, G, N)
    Cm = xBC[..., di + G * N:].reshape(b, S, G, N)
    A = -jnp.exp(p["a_log"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm.ssd(x, dt, A, Bm, Cm) + x * p["d_skip"][:, None]
    y = (y.reshape(b, S, di) * jax.nn.silu(z)).reshape(b, S, G, di // G)
    y = rms_norm(y, p["norm_g"].reshape(G, di // G), eps).reshape(b, S, di)
    return matmul(y, p["out_proj"], rnd)


def shared_block(p, A, B, h, x0, m, rnd):
    """T for one application: block weights ``p``, its adapter A (d, r)
    and B (2, r, d_ff)."""
    eps = m.get("norm_eps", 1e-5)
    b, S, _ = h.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]
    y = rms_norm(jnp.concatenate([h, x0], -1), p["ln1"], eps)
    theta = m.get("rope_theta", 10000.0)
    q = rope(matmul(y, a["wq"], rnd).reshape(b, S, H, hd), theta)
    k = rope(matmul(y, a["wk"], rnd).reshape(b, S, Hkv, hd), theta)
    v = matmul(y, a["wv"], rnd).reshape(b, S, Hkv, hd)
    o = causal_attention(q, k, v, (hd / 2) ** -0.5).reshape(b, S, H * hd)
    y = rms_norm(matmul(o, a["wo"], rnd), p["ln2"], eps)
    f = p["ffn"]
    low = matmul(y, A, rnd)
    g = matmul(y, f["w_gate"], rnd) + matmul(low, B[0], rnd)
    u = matmul(y, f["w_up"], rnd) + matmul(low, B[1], rnd)
    return matmul(jax.nn.gelu(g, approximate=False) * u, f["w_down"], rnd)


def forward(params, tokens, m, rnd=exact, positions=None, remat=False):
    """Logits (b, len(positions) or S, vocab) in float32, the layers in
    order: each run of plain Mamba2 layers as one ``lax.scan``, each
    hybrid layer on its own."""
    eps = m.get("norm_eps", 1e-5)
    ids = list(m["hybrid_layer_ids"])
    nb = m["n_shared_blocks"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    blocks = params["blocks"]
    layer_at = lambda i: f32(jax.tree.map(lambda t: t[i], blocks))

    def plain(h, bp):
        return h + mamba2(bp["ssm"], rms_norm(h, bp["ln"], eps), m, rnd)

    def hybrid(h, x0, bp, shared, A, B, L):
        t = shared_block(shared, A, B, h, x0, m, rnd)
        return h + mamba2(bp["ssm"], rms_norm(h + matmul(t, L, rnd),
                                              bp["ln"], eps), m, rnd)

    if remat:
        plain = jax.checkpoint(plain)
        hybrid = jax.checkpoint(hybrid)
    x0 = ssm.embed(params, tokens)
    h = x0
    edges = sorted(set([0] + ids + [i + 1 for i in ids] +
                       [m["num_layers"]]))
    for lo, hi in zip(edges[:-1], edges[1:]):
        if lo in ids:
            j = ids.index(lo)
            h = hybrid(h, x0, layer_at(lo),
                       f32(jax.tree.map(lambda t: t[j % nb],
                                        params["shared_blocks"])),
                       params["adapter"]["in_proj"][j].astype(F32),
                       params["adapter"]["out_proj"][j].astype(F32),
                       params["shared_out"]["out_proj"][j].astype(F32))
        else:
            seg = f32(jax.tree.map(lambda t: t[lo:hi], blocks))
            h, _ = lax.scan(lambda h, bp: (plain(h, bp), None), h, seg)
    return ssm.head(params, h, m, rnd, positions)


def loss(params, batch, m, rnd=exact):
    return ssm.loss(params, batch, m, rnd, forward_fn=forward)
