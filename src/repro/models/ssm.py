"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

The SSD layer computes, per head h and state size N:

    h_t = exp(dt_t * A_h) * h_{t-1} + dt_t * B_t x_t^T        (N x hd state)
    y_t = C_t^T h_t  (+ D_h * x_t)

Training/prefill uses the *chunked* SSD algorithm: the sequence is split
into chunks of Q tokens; within a chunk the output is a masked quadratic
form (the "attention-like" dual), across chunks the state is carried by a
scan with scalar per-head decays.  This is O(S·Q) compute/memory instead of
O(S²) and maps directly onto the MXU — the Pallas kernel in
``repro.kernels.ssd_scan`` implements the intra-chunk part with VMEM tiling;
this file is its jnp oracle and the production fallback.

Decode maintains (state, conv buffer) and performs the O(1) recurrence.

TP note: heads are independent except through the channel-mixing in/out
projections, so the layer shards over the ``model`` axis on heads/d_inner
('ssm_in' logical axis), exactly like attention head-TP.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .modules import AxisNames, Box, dense_init, zeros_init, ones_init, rms_norm


class SSMState(NamedTuple):
    """Per-layer decode state."""
    h: jnp.ndarray        # (B, H, hd, N) SSM state
    conv: jnp.ndarray     # (B, d_conv-1, conv_dim) conv lag buffer


def init_mamba2(key, cfg, dtype=jnp.float32):
    d, di = cfg.d_model, cfg.d_inner
    H, hd, N, G = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_groups
    conv_dim = di + 2 * G * N
    ks = jax.random.split(key, 6)
    # in_proj emits [z (gate), x, B, C, dt]
    d_in_proj = 2 * di + 2 * G * N + H
    params = {
        "in_proj": dense_init(ks[0], (d, d_in_proj), ("embed", "ssm_in"), dtype=dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_dim), ("null", "ssm_in"),
                             scale=1.0 / math.sqrt(cfg.ssm_conv), dtype=dtype),
        "conv_b": zeros_init((conv_dim,), ("ssm_in",), dtype),
        "a_log": Box(jnp.log(jnp.linspace(1.0, 16.0, H, dtype=dtype)), AxisNames("ssm_head")),
        "dt_bias": Box(jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (H,), dtype) *
                    (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)))), AxisNames("ssm_head")),
        "d_skip": ones_init((H,), ("ssm_head",), dtype),
        "norm_g": ones_init((di,), ("ssm_in",), dtype),
        "out_proj": dense_init(ks[3], (di, d), ("ssm_in", "embed"), dtype=dtype),
    }
    return params


def _split_in_proj(zxbcdt, cfg):
    di, G, N, H = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * G * N]
    dt = zxbcdt[..., di + di + 2 * G * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, conv_b, lag=None):
    """Depthwise causal conv1d.  xBC: (B,S,C); conv_w: (K,C).

    ``lag``: optional (B, K-1, C) left-context (decode buffer). Returns
    (out, new_lag)."""
    K = conv_w.shape[0]
    B, S, C = xBC.shape
    if lag is None:
        lag = jnp.zeros((B, K - 1, C), xBC.dtype)
    xfull = jnp.concatenate([lag, xBC], axis=1)               # (B, S+K-1, C)
    out = jnp.zeros((B, S, C), jnp.float32)
    for i in range(K):
        out = out + xfull[:, i:i + S].astype(jnp.float32) * conv_w[i].astype(jnp.float32)
    out = jax.nn.silu(out + conv_b.astype(jnp.float32)).astype(xBC.dtype)
    new_lag = xfull[:, S:]
    return out, new_lag


def _segsum(log_a):
    """(..., Q) → (..., Q, Q) lower-triangular cumulative log-decay:
    segsum[i, j] = sum_{k=j+1..i} log_a[k] for i >= j, -inf otherwise."""
    Q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]                # i,j → cs_i - cs_j
    ii = jnp.arange(Q)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bmat, Cmat, *, chunk: int = 128,
                initial_state=None, return_state: bool = False):
    """Chunked SSD scan.

    x:    (B, S, H, hd)   — per-head inputs
    dt:   (B, S, H)       — positive step sizes (softplus already applied)
    A:    (H,)            — negative per-head decay rates
    Bmat: (B, S, G, N);  Cmat: (B, S, G, N) with H % G == 0
    Returns y: (B, S, H, hd) (+ final state (B,H,hd,N) if requested).
    """
    Bsz, S, H, hd = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))

    xc = x.reshape(Bsz, nc, Q, H, hd)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(jnp.float32)
    Bc = Bmat.reshape(Bsz, nc, Q, G, N)
    Cc = Cmat.reshape(Bsz, nc, Q, G, N)

    dA = dtc * A.astype(jnp.float32)[None, None, None, :]     # (B,nc,Q,H) ≤ 0
    seg = _segsum(jnp.moveaxis(dA, -1, -2))                   # (B,nc,H,Q,Q)

    # ---- intra-chunk (quadratic dual) -------------------------------------
    Bh = jnp.repeat(Bc, rep, axis=3)                          # (B,nc,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Ch, Bh,
                        preferred_element_type=jnp.float32)
    L = jnp.exp(seg)                                          # (B,nc,H,Q,Q)
    M = scores * L * jnp.moveaxis(dtc, -1, -2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchqk,bckhd->bcqhd", M.astype(x.dtype), xc)

    # ---- chunk states ------------------------------------------------------
    decay_to_end = jnp.exp(jnp.cumsum(dA, axis=2)[:, :, -1:, :] -
                           jnp.cumsum(dA, axis=2))            # (B,nc,Q,H)
    states = jnp.einsum("bcqhn,bcqh,bcqhd->bchdn", Bh.astype(jnp.float32),
                        dtc * decay_to_end, xc.astype(jnp.float32))
    # (B,nc,H,hd,N) fp32
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                # (B,nc,H)

    # ---- inter-chunk scan (associative, log-depth) --------------------------
    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H, hd, N), states.dtype)

    def combine(a, b):
        (da, sa), (db, sb) = a, b
        return (da * db, sa * db[..., None, None] + sb)

    decays = jnp.moveaxis(chunk_decay, 1, 0)                  # (nc,B,H)
    sts = jnp.moveaxis(states, 1, 0)                          # (nc,B,H,hd,N)
    # prepend initial state as a chunk with decay 1
    decays = jnp.concatenate([jnp.ones_like(decays[:1]), decays], axis=0)
    sts = jnp.concatenate([initial_state[None].astype(sts.dtype), sts], axis=0)
    acc_decay, acc_state = jax.lax.associative_scan(combine, (decays, sts), axis=0)
    prev_states = acc_state[:-1]                              # state entering chunk c
    final_state = acc_state[-1]

    # ---- inter-chunk contribution ------------------------------------------
    in_decay = jnp.exp(jnp.cumsum(dA, axis=2))                # decay from chunk start
    y_inter = jnp.einsum("bcqhn,bchdn,bcqh->bcqhd", Ch,
                         jnp.moveaxis(prev_states, 0, 1).astype(jnp.float32),
                         in_decay).astype(x.dtype)

    y = (y_intra.astype(jnp.float32) + y_inter.astype(jnp.float32)).astype(x.dtype)
    y = y.reshape(Bsz, nc * Q, H, hd)[:, :S]
    if return_state:
        return y, final_state
    return y


def ssd_reference(x, dt, A, Bmat, Cmat, initial_state=None, return_state=False):
    """Sequential per-token recurrence — the bit-exact oracle for tests."""
    Bsz, S, H, hd = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    h0 = (jnp.zeros((Bsz, H, hd, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, Bt, Ct = inp                                 # (B,H,hd),(B,H),(B,G,N),(B,G,N)
        Bh = jnp.repeat(Bt, rep, axis=1)
        Ch = jnp.repeat(Ct, rep, axis=1)
        decay = jnp.exp(dtt.astype(jnp.float32) * A.astype(jnp.float32))
        upd = jnp.einsum("bh,bhd,bhn->bhdn", dtt.astype(jnp.float32),
                         xt.astype(jnp.float32), Bh.astype(jnp.float32))
        h = h * decay[..., None, None] + upd
        y = jnp.einsum("bhn,bhdn->bhd", Ch.astype(jnp.float32), h)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bmat, 1, 0), jnp.moveaxis(Cmat, 1, 0))
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    if return_state:
        return y, hT
    return y


def mamba2_forward(params, u, cfg, *, chunk: int = 128,
                   state: SSMState | None = None, return_state: bool = False):
    """Full Mamba2 mixer.  u: (B, S, d_model) → (B, S, d_model)."""
    with jax.named_scope("ssm"):
        B, S, d = u.shape
        H, hd = cfg.ssm_heads, cfg.ssm_headdim
        N, G = cfg.ssm_state, cfg.ssm_groups
        di = cfg.d_inner

        zxbcdt = u @ params["in_proj"]
        z, xBC, dt = _split_in_proj(zxbcdt, cfg)
        lag = state.conv if state is not None else None
        xBC, new_lag = _causal_conv(xBC, params["conv_w"], params["conv_b"],
                                    lag)
        x = xBC[..., :di].reshape(B, S, H, hd)
        Bmat = xBC[..., di:di + G * N].reshape(B, S, G, N)
        Cmat = xBC[..., di + G * N:].reshape(B, S, G, N)
        A = -jnp.exp(params["a_log"].astype(jnp.float32))
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             params["dt_bias"].astype(jnp.float32))  # (B,S,H)

        h0 = state.h if state is not None else None
        with jax.named_scope("ssd"):
            if S == 1 and state is not None:
                # O(1) decode recurrence
                decay = jnp.exp(dt[:, 0] * A)                          # (B,H)
                Bh = jnp.repeat(Bmat[:, 0], H // G, axis=1)
                Ch = jnp.repeat(Cmat[:, 0], H // G, axis=1)
                upd = jnp.einsum("bh,bhd,bhn->bhdn", dt[:, 0],
                                 x[:, 0].astype(jnp.float32),
                                 Bh.astype(jnp.float32))
                h = state.h.astype(jnp.float32) * decay[..., None, None] + upd
                y = jnp.einsum("bhn,bhdn->bhd", Ch.astype(jnp.float32),
                               h)[:, None]
                y = y.astype(u.dtype)
                hT = h
            else:
                y, hT = ssd_chunked(x, dt, A, Bmat, Cmat, chunk=chunk,
                                    initial_state=h0, return_state=True)

        y = y + x * params["d_skip"].astype(u.dtype)[None, None, :, None]
        y = y.reshape(B, S, di)
        # gated RMSNorm (mamba2's norm-before-out), over each group's
        # d_inner / G channels
        if G == 1:
            y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                         params["norm_g"], cfg.norm_eps)
        else:
            y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
            y = rms_norm(y.reshape(B, S, G, di // G),
                         params["norm_g"].reshape(G, di // G),
                         cfg.norm_eps).reshape(B, S, di)
        out = y @ params["out_proj"]
        if return_state:
            return out, SSMState(h=hT, conv=new_lag)
        return out


def init_ssm_state(cfg, batch: int, dtype=jnp.float32) -> SSMState:
    H, hd, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * N
    return SSMState(
        h=jnp.zeros((batch, H, hd, N), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    )
