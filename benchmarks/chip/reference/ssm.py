"""Plain float32 Mamba2 language model: the reference for the ``ssm``
family, and the Mamba2 layer that ``reference.hybrid`` builds on.

Written from the Mamba2 paper (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060): the layer of its Figure 6 (input projection to
[z, x, B, C, dt], depthwise causal conv and SiLU on [x, B, C], softplus
step, the SSD recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
y_t = C_t h_t + D x_t, gated RMSNorm, output projection), with the SSD
computed by the paper's minimal chunked algorithm (its Listing 1) in
float32.  It imports nothing of the program under test: it reads the
weights the benchmark made, laid out as the program keeps them
(``blocks`` stacked on a leading layer axis).

Every matmul goes through ``matmul(a, b, rnd)``: ``exact`` keeps float32
at ``HIGHEST`` precision; ``fp8`` rounds both operands to float8 e4m3
with a per-tensor scale, the control a correct run must not look like.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def exact(x):
    return x.astype(F32)


def fp8(x):
    """Rounded to float8 e4m3 with a per-tensor scale on the way forward;
    the gradient passes straight through in float32, as in fp8 training,
    where the backward matmuls see the rounded operands but their
    cotangents keep their precision (a cast's own gradient would round
    them to fp8 unscaled and flush the small ones to zero)."""
    x = x.astype(F32)
    s = lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0)
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + lax.stop_gradient(q - x)


ROUNDING = {"exact": exact, "fp8": fp8}


def matmul(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def einsum(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def segsum(x):
    """(..., T) -> (..., T, T): out[i, j] = sum of x[j+1..i], -inf above
    the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    cs = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), cs, -jnp.inf)


def ssd(x, dt, A, B, C, chunk: int = 64):
    """x (b,S,H,P), dt (b,S,H), A (H,), B and C (b,S,G,N) -> y (b,S,H,P)."""
    b, S, H, P = x.shape
    rep = H // B.shape[2]
    pad = (-S) % chunk
    X = x * dt[..., None]
    Ad = dt * A
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    if pad:
        z = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        X, Ad, Bh, Ch = z(X), z(Ad), z(Bh), z(Ch)
    c = (S + pad) // chunk
    N = Bh.shape[-1]
    X = X.reshape(b, c, chunk, H, P)
    Bh = Bh.reshape(b, c, chunk, H, N)
    Ch = Ch.reshape(b, c, chunk, H, N)
    Ad = Ad.reshape(b, c, chunk, H).transpose(0, 3, 1, 2)      # b h c l
    Acum = jnp.cumsum(Ad, axis=-1)

    L = jnp.exp(segsum(Ad))                                     # b h c l s
    scores = einsum("bclhn,bcshn->bhcls", Ch, Bh)
    y_diag = einsum("bhcls,bcshp->bclhp", scores * L, X)

    decay_states = jnp.exp(Acum[..., -1:] - Acum)               # b h c l
    states = einsum("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(Acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = einsum("bclhn,bchpn,bhcl->bclhp", Ch, states, jnp.exp(Acum))
    return (y_diag + y_off).reshape(b, c * chunk, H, P)[:, :S]


def mamba2(p, u, m, rnd):
    """One Mamba2 mixer.  p: the layer's ``ssm`` weights; u (b,S,d)."""
    b, S, d = u.shape
    di = m["ssm_expand"] * d
    P, N, G = m["ssm_headdim"], m["ssm_state"], m.get("ssm_groups", 1)
    H = di // P
    p = jax.tree.map(lambda t: t.astype(F32), p)
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
    y = ssd(x, dt, A, Bm, Cm) + x * p["d_skip"][:, None]
    y = y.reshape(b, S, di) * jax.nn.silu(z)
    y = rms_norm(y, p["norm_g"], m.get("norm_eps", 1e-5))
    return matmul(y, p["out_proj"], rnd)


def head(params, x, m, rnd, positions=None):
    """Final norm and LM head over the real vocabulary, at ``positions``
    (all when None)."""
    if positions is not None:
        x = x[:, positions]
    x = rms_norm(x, params["final_norm"].astype(F32), m.get("norm_eps", 1e-5))
    w = (params["embed"].T if m.get("tie_embeddings") else params["lm_head"])
    return matmul(x, w[:, :m["vocab_size"]], rnd)


def embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


def forward(params, tokens, m, rnd=exact, positions=None, remat=False):
    """Logits (b, len(positions) or S, vocab) in float32, one layer at a
    time (``lax.scan`` over the stacked layers)."""
    eps = m.get("norm_eps", 1e-5)

    def layer(h, bp):
        return h + mamba2(bp["ssm"], rms_norm(h, bp["ln"].astype(F32), eps),
                          m, rnd), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = lax.scan(layer, embed(params, tokens), params["blocks"])
    return head(params, x, m, rnd, positions)


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def loss(params, batch, m, rnd=exact, forward_fn=None):
    fwd = forward_fn or forward
    return cross_entropy(fwd(params, batch["tokens"], m, rnd, remat=True),
                         batch["labels"])
