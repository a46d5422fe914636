"""Per-architecture smoke tests (reduced configs, CPU) + model invariants.

Required by the task spec: every assigned arch instantiates a REDUCED
same-family config and runs one forward/train step asserting output shapes
and no NaNs.  Full configs are exercised only via the dry-run.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import transformer as tfm
from repro.models.attention import (apply_rope, chunked_attention,
                                    dense_attention, repeat_kv)
from repro.models.config import ParallelConfig
from repro.models.modules import split
from repro.models.ssm import ssd_chunked, ssd_reference
from repro.models.whisper import encode

PCFG = ParallelConfig(remat="none")
KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=32):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_patches, cfg.d_model)) * 0.02
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            KEY, (B, cfg.enc_seq, cfg.d_model)) * 0.02
    return batch


def enc_fn_for(cfg):
    if cfg.family != "audio":
        return None
    return lambda p, b: encode(p, b, cfg, PCFG)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward(arch):
    cfg = get_config(arch).reduced()
    params, axes = split(tfm.init(KEY, cfg))
    batch = make_batch(cfg)
    loss, metrics = jax.jit(
        lambda p, b: tfm.loss_fn(p, b, cfg, PCFG, enc_fn=enc_fn_for(cfg))
    )(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"
    assert float(loss) == pytest.approx(np.log(cfg.vocab_size), rel=0.15)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b",
                                  "mamba2-1.3b", "zamba2-2.7b"])
def test_arch_smoke_train_step(arch):
    """One full optimizer step decreases loss on a repeated batch."""
    from repro.train.optim import OptimConfig, adam_update, init_adam
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params, _ = split(tfm.init(KEY, cfg))
    batch = make_batch(cfg)
    ocfg = OptimConfig(lr=5e-3, warmup_steps=0, weight_decay=0.0)
    opt = init_adam(params, ocfg)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, batch, cfg, PCFG), has_aux=True)(params)
        params, opt, _ = adam_update(params, grads, opt, ocfg)
        return params, opt, loss

    losses = []
    for _ in range(5):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], f"{arch}: loss did not decrease {losses}"


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_matches_prefill(arch):
    cfg = get_config(arch).reduced()
    if cfg.n_experts:  # capacity dropping differs between runs — disable
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    params, _ = split(tfm.init(KEY, cfg))
    B, S, S0, CACHE = 2, 20, 16, 32
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    batch = make_batch(cfg, B, S0)
    batch["tokens"] = toks[:, :S0]
    enc = enc_fn_for(cfg)
    logits, state = tfm.prefill(params, batch, cfg, PCFG, CACHE, enc_fn=enc)
    outs = [logits]
    for t in range(S0, S):
        lg, state = tfm.decode_step(params, toks[:, t:t + 1], state, cfg, PCFG)
        outs.append(lg)
    for t, lg in zip(range(S0, S + 1), outs):
        b2 = dict(batch)
        b2["tokens"] = toks[:, :t]
        ref, _ = tfm.prefill(params, b2, cfg, PCFG, CACHE, enc_fn=enc)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                                   atol=2e-3, rtol=2e-2)


# --------------------------------------------------------------------------
# attention invariants
# --------------------------------------------------------------------------

def test_chunked_matches_dense():
    B, S, H, hd = 2, 100, 3, 16
    q = jax.random.normal(KEY, (B, S, H, hd)) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd))
    for causal in (True, False):
        for window in (0, 17):
            out = chunked_attention(q, k, v, causal=causal, window=window,
                                    q_chunk=32, k_chunk=16)
            ref = dense_attention(q, k, v, causal=causal, window=window)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)


def test_gqa_repeat_equivalence():
    """GQA with repeated KV == MHA with shared heads."""
    B, S, Hq, Hkv, hd = 2, 24, 4, 2, 8
    q = jax.random.normal(KEY, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, hd))
    out = dense_attention(q, k, v)
    out2 = dense_attention(q, repeat_kv(k, 2), repeat_kv(v, 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


def test_rope_preserves_norm_and_relativity():
    B, S, H, hd = 1, 16, 2, 32
    x = jax.random.normal(KEY, (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    r = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(r, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # relativity: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 1, 1, hd))
    def dot_at(p, d):
        pq = jnp.full((1, 1), p)
        pk = jnp.full((1, 1), p + d)
        return float(jnp.sum(apply_rope(q, pq, 1e4) * apply_rope(k, pk, 1e4)))
    assert dot_at(0, 3) == pytest.approx(dot_at(7, 3), abs=1e-4)


def test_swa_masks_out_of_window():
    B, S, H, hd = 1, 32, 1, 8
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd))
    v0 = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd))
    # perturbing v outside the window must not change the last query's out
    w = 8
    v1 = v0.at[:, : S - w].add(100.0)
    o0 = dense_attention(q, k, v0, causal=True, window=w)
    o1 = dense_attention(q, k, v1, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(o0[:, -1]), np.asarray(o1[:, -1]),
                               atol=1e-5)


# --------------------------------------------------------------------------
# SSD invariants
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_ssd_chunked_matches_reference():
    B, S, H, hd, G, N = 2, 50, 4, 8, 2, 6
    x = jax.random.normal(KEY, (B, S, H, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, G, N)) * 0.4
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, G, N)) * 0.4
    for chunk in (8, 16, 64):
        y = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        yr = ssd_reference(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_ssd_state_carry():
    """Running two halves with carried state == one full run."""
    B, S, H, hd, G, N = 1, 40, 2, 8, 1, 4
    x = jax.random.normal(KEY, (B, S, H, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, G, N)) * 0.4
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, G, N)) * 0.4
    full, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=8, return_state=True)
    h = S // 2
    y1, st = ssd_chunked(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h],
                         chunk=8, return_state=True)
    y2 = ssd_chunked(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:],
                     chunk=8, initial_state=st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(full), atol=2e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# block scopes: op_name metadata only
# --------------------------------------------------------------------------

SCOPES = {"embed", "ssm", "ssd", "attention", "mlp", "moe", "lm_head",
          "loss", "optimizer", "adapter", "shared_in"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
METADATA = re.compile(r",? metadata=\{[^}]*\}")
MATMUL = re.compile(r"\s(dot|convolution)\(")
NAME = re.compile(r"%[\w.-]+")


def _lowered(program):
    """(jitted program, its arguments) of one scoped program at a tiny
    size: zamba2 prefill and decode, a mixtral prefill (moe), a mamba2
    and a zamba2-7b train step with block remat (loss, optimizer, the
    backward pass; zamba2-7b's adapter and shared_in)."""
    from repro.train.optim import OptimConfig, adam_update, init_adam
    arch, kind = program.split(":")
    cfg = get_config(arch).reduced()
    params, _ = split(tfm.init(KEY, cfg, dtype=jnp.bfloat16))
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    if kind == "prefill":
        def prefill(p, t):
            return tfm.prefill(p, {"tokens": t}, cfg, PCFG, 32)
        return jax.jit(prefill), (params, toks)
    if kind == "decode":
        state = jax.eval_shape(lambda p, t: tfm.prefill(
            p, {"tokens": t}, cfg, PCFG, 32)[1], params, toks)

        def decode_step(p, t, s):
            return tfm.decode_step(p, t, s, cfg, PCFG)
        return jax.jit(decode_step), (params, toks[:, :1], state)
    pcfg = ParallelConfig(remat="block")
    ocfg = OptimConfig()

    def train_step(p, opt, batch):
        (_, m), g = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, batch, cfg, pcfg), has_aux=True)(p)
        p, opt, _ = adam_update(p, g, opt, ocfg)
        return p, opt, m["loss"]
    return jax.jit(train_step), (params, init_adam(params, ocfg),
                                 {"tokens": toks, "labels": toks})


def _scopes(op_name):
    """Scope names among an op_name's path components, wrappers such as
    jvp(...) or transpose(...) stripped."""
    return SCOPES & set(re.findall(r"\w+", op_name))


SCOPED_PROGRAMS = {
    "zamba2-2.7b:prefill": {"embed", "ssm", "ssd", "attention", "mlp",
                            "lm_head"},
    "zamba2-2.7b:decode": {"embed", "ssm", "ssd", "attention", "mlp",
                           "lm_head"},
    "mixtral-8x7b:prefill": {"embed", "attention", "moe", "lm_head"},
    "mamba2-1.3b:train": {"embed", "ssm", "ssd", "lm_head", "loss",
                          "optimizer"},
    "zamba2-7b:train": {"embed", "ssm", "ssd", "attention", "mlp",
                        "adapter", "shared_in", "lm_head", "loss",
                        "optimizer"},
}


@pytest.mark.parametrize("program", sorted(SCOPED_PROGRAMS))
def test_block_scopes_in_compiled_hlo(program):
    """Every block kind the program runs names its ops in the compiled
    HLO, and every matmul jax writes lies in some scope.  Matmuls that
    XLA's CPU passes build anew carry no metadata at all (the SSD
    einsums' batched dots at these shapes); they are not jax's to name."""
    fn, args = _lowered(program)
    lowered = fn.lower(*args)
    names = OP_NAME.findall(lowered.compile().as_text())
    found = set().union(*map(_scopes, names))
    assert found == SCOPED_PROGRAMS[program]
    text = lowered.as_text(dialect="hlo", debug_info=True)
    matmuls = [line for line in text.splitlines() if MATMUL.search(line)]
    assert matmuls
    for line in matmuls:
        name = OP_NAME.search(line)
        assert name and _scopes(name.group(1)), line


@pytest.mark.parametrize("program", sorted(SCOPED_PROGRAMS))
def test_block_scopes_leave_compiled_ops_unchanged(program, monkeypatch):
    """The compiled program runs the same ops in the same order with the
    scopes as without them: its text is the same once metadata and the
    source tables are stripped and every instruction and computation is
    renamed by first appearance (XLA names instructions from their
    source locations, which the scopes are part of)."""
    import contextlib

    def compiled():
        jax.clear_caches()
        fn, args = _lowered(program)
        lines = fn.lower(*args).compile().as_text().splitlines()
        first = next(i for i, line in enumerate(lines)
                     if line.startswith(("%", "ENTRY")))
        names = {}
        return NAME.sub(
            lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
            METADATA.sub("", "\n".join(lines[:1] + lines[first:])))

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "op_name" not in scoped
    assert scoped == bare


# --------------------------------------------------------------------------
# hybrid stack: one scan over the stacked layers against a layer loop
# --------------------------------------------------------------------------

def _hybrid_layer_loop(params, cfg, x, positions, mode, *, ssm=None,
                       shared_kv=None, cache_index=None, cache_len=None):
    """The oracle: each Mamba2 layer in turn, the shared attention+MLP block
    after every ``attn_every``-th, each application with its own KV entry.
    Returns (x, stacked new SSM state, stacked new shared KV)."""
    from repro.models.layers import apply_attn_block
    from repro.models.modules import rms_norm
    from repro.models.ssm import mamba2_forward
    take = lambda tree, i: (None if tree is None
                            else jax.tree.map(lambda a: a[i], tree))
    new_ssm, new_kv = [], []
    for i in range(cfg.num_layers):
        bp = take(params["blocks"], i)
        hin = rms_norm(x, bp["ln"], cfg.norm_eps)
        if mode == "train":
            out = mamba2_forward(bp["ssm"], hin, cfg)
        else:
            out, st = mamba2_forward(bp["ssm"], hin, cfg, state=take(ssm, i),
                                     return_state=True)
            new_ssm.append(st)
        x = x + out
        if i % cfg.attn_every == cfg.attn_every - 1:
            x, kv, _, _ = apply_attn_block(
                params["shared_attn"], cfg, PCFG, x, positions=positions,
                mode=mode, cache=take(shared_kv, i // cfg.attn_every),
                cache_index=cache_index, cache_len=cache_len)
            new_kv.append(kv)
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs) if xs else None
    return x, stack(new_ssm), stack(new_kv)


def _oracle_logits(params, cfg, x):
    from repro.models.modules import rms_norm
    return rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]


def _hybrid_serve_oracle(params, cfg, toks, S0, cache_len):
    """Prompt toks[:, :S0], then one decode step a further token: the
    logits of every step and the last state, by the layer loop."""
    x = jnp.take(params["embed"], toks[:, :S0], axis=0)
    pos = jnp.broadcast_to(jnp.arange(S0)[None], toks[:, :S0].shape)
    x, ssm, kv = _hybrid_layer_loop(params, cfg, x, pos, "prefill",
                                    cache_len=cache_len)
    outs = [_oracle_logits(params, cfg, x[:, -1])]
    for t in range(S0, toks.shape[1]):
        x = jnp.take(params["embed"], toks[:, t:t + 1], axis=0)
        pos = jnp.full((toks.shape[0], 1), t, jnp.int32)
        x, ssm, kv = _hybrid_layer_loop(
            params, cfg, x, pos, "decode", ssm=ssm, shared_kv=kv,
            cache_index=jnp.int32(t))
        outs.append(_oracle_logits(params, cfg, x[:, 0]))
    return outs, ssm, kv


def _hybrid_serve(params, cfg, pcfg, toks, S0, cache_len):
    logits, state = tfm.prefill(params, {"tokens": toks[:, :S0]}, cfg, pcfg,
                                cache_len)
    outs = [logits]
    for t in range(S0, toks.shape[1]):
        logits, state = tfm.decode_step(params, toks[:, t:t + 1], state, cfg,
                                        pcfg)
        outs.append(logits)
    return outs, state.ssm, state.shared_kv


def _lowered_lines(cfg, groups):
    """Instruction count of the lowered hybrid decode step with ``groups``
    applications of the shared block."""
    cfg = dataclasses.replace(cfg, num_layers=groups * cfg.attn_every)
    params = jax.eval_shape(lambda: split(tfm.init(KEY, cfg))[0])
    state = jax.eval_shape(lambda: tfm.init_decode_state(cfg, 2, 32))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = jax.jit(lambda p, t, s: tfm.decode_step(p, t, s, cfg, PCFG)).lower(
        params, tok, state).as_text()
    return sum(" = " in line for line in text.splitlines())


@pytest.mark.parametrize("case", ["serve", "loss_grad", "unrolled",
                                  "size_by_groups"])
def test_hybrid_scan_matches_layer_loop(case):
    """zamba2's stack runs as one scan over its stacked Mamba2 layers with
    the shared block inside it; it computes what a plain layer-by-layer
    loop computes: prompt and decode logits, SSM state and shared KV
    (``serve``, and unrolled with ``scan_layers=False``), the loss and its
    gradients (``loss_grad``).  ``size_by_groups``: the lowered decode
    program does not grow with the number of groups."""
    cfg = get_config("zamba2-2.7b").reduced()
    assert cfg.num_layers // cfg.attn_every >= 2
    if case == "size_by_groups":
        assert _lowered_lines(cfg, 4) == _lowered_lines(cfg, 2)
        return
    params, _ = split(tfm.init(KEY, cfg))
    toks = jax.random.randint(KEY, (2, 20), 0, cfg.vocab_size)
    close = dict(atol=1e-5, rtol=1e-5)
    if case == "loss_grad":
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

        def oracle_loss(p):
            x = jnp.take(p["embed"], toks, axis=0)
            pos = jnp.broadcast_to(jnp.arange(toks.shape[1])[None], toks.shape)
            x, _, _ = _hybrid_layer_loop(p, cfg, x, pos, "train")
            from repro.models.modules import softmax_cross_entropy
            return softmax_cross_entropy(_oracle_logits(p, cfg, x),
                                         batch["labels"], cfg.vocab_size)[0]

        for remat in ("none", "block"):
            pcfg = ParallelConfig(remat=remat)
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p: tfm.loss_fn(p, batch, cfg, pcfg)[0]))(params)
            ref, ref_grads = jax.jit(jax.value_and_grad(oracle_loss))(params)
            np.testing.assert_allclose(float(loss), float(ref), **close)
            for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                           atol=1e-5, rtol=1e-4)
        return
    pcfg = ParallelConfig(remat="none", scan_layers=case != "unrolled")
    got = jax.jit(_hybrid_serve, static_argnums=(1, 2, 4, 5))(
        params, cfg, pcfg, toks, 16, 32)
    ref = jax.jit(_hybrid_serve_oracle, static_argnums=(1, 3, 4))(
        params, cfg, toks, 16, 32)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), **close)


# the published schedules: at which layers a shared block runs
SCHEDULES = {
    ("zamba2-2.7b", "full"): (5, 11, 17, 23, 29, 35, 41, 47, 53),
    ("zamba2-2.7b", "reduced"): (1, 3),
    ("zamba2-7b", "full"): (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71,
                            77),
    ("zamba2-7b", "reduced"): (1, 3, 5),
}


@pytest.mark.parametrize("arch,size", sorted(SCHEDULES))
def test_shared_block_schedule(arch, size):
    """The layers at which a shared block runs, as the published configs
    give them (zamba2-2.7b: after every sixth of 54; zamba2-7b: before
    each of its 13 ``hybrid_layer_ids``), how many applications that is,
    and the shared KV cache's one entry per application."""
    cfg = get_config(arch)
    if size == "reduced":
        cfg = cfg.reduced()
    layers = SCHEDULES[arch, size]
    assert cfg.application_layers == layers
    assert cfg.n_applications == len(layers)
    kv = jax.eval_shape(lambda: tfm._shared_kv_zeros(cfg, 2, 8, jnp.bfloat16))
    assert kv.k.shape[0] == kv.v.shape[0] == len(layers)


# --------------------------------------------------------------------------
# zamba2: Zyphra's published block against the benchmark's plain reference
# --------------------------------------------------------------------------

def _zamba2_reference():
    """``benchmarks/chip/reference/zamba2.py``: plain float32, written from
    the published equations, importing nothing of the program."""
    import sys
    from pathlib import Path
    bench = str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import zamba2
    return zamba2


def _zamba2_tiny():
    """12 layers with hybrid ids 3, 7, 11: block A runs at 3 and 11, B at
    7; rank-4 adapters, 2 B/C groups, d 64, MHA of 4 heads of 16 (the
    published model's heads are all KV heads too)."""
    cfg = dataclasses.replace(get_config("zamba2-7b").reduced(),
                              num_layers=12, hybrid_layer_ids=(3, 7, 11),
                              adapter_rank=4, n_kv_heads=4)
    assert cfg.ssm_groups == 2 and cfg.n_shared_blocks == 2
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return cfg, m


def _zamba2_logits(params, cfg, toks):
    x, positions = tfm._embed_inputs(params, cfg, {"tokens": toks},
                                     lambda t, kind="residual": t)
    x = tfm._scan_blocks(params, cfg, PCFG, x, positions,
                         lambda t, kind="residual": t)[0]
    return tfm._lm_head(params, cfg, x, lambda t, kind="residual": t
                        )[..., :cfg.vocab_size]


# Both sides compute in float32 at HIGHEST; what is left is summation
# order (SSD chunks of 128 against the reference's 64, blocked against
# plain softmax), about 1e-6 on logits of about 1 here.  A wrong
# application index, block or group norm moves them by 1e-2 or more.
ZAMBA2_TOL = dict(atol=2e-5, rtol=2e-5)


def test_zamba2_forward_matches_reference():
    ref = _zamba2_reference()
    cfg, m = _zamba2_tiny()
    params, _ = split(tfm.init(KEY, cfg))
    toks = jax.random.randint(KEY, (2, 40), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: _zamba2_logits(p, cfg, t))(params, toks)
        want = jax.jit(lambda p, t: ref.forward(p, t, m))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **ZAMBA2_TOL)


def test_zamba2_prefill_decode_match_reference():
    """The prompt through ``prefill``, then each further token through
    ``decode_step`` and the per-application KV cache, against the
    reference's full forward at every position."""
    ref = _zamba2_reference()
    cfg, m = _zamba2_tiny()
    params, _ = split(tfm.init(KEY, cfg))
    S0, S = 16, 24
    toks = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, state = jax.jit(lambda p, t: tfm.prefill(
            p, {"tokens": t}, cfg, PCFG, 32))(params, toks[:, :S0])
        assert state.shared_kv.k.shape == (3, 2, 32, 4, 16)
        step = jax.jit(lambda p, t, s: tfm.decode_step(p, t, s, cfg, PCFG))
        outs = [logits]
        for t in range(S0, S - 1):
            logits, state = step(params, toks[:, t:t + 1], state)
            outs.append(logits)
        want = jax.jit(lambda p, t: ref.forward(
            p, t, m, positions=np.arange(S0 - 1, S - 1)))(params, toks)
    got = jnp.stack(outs, axis=1)[..., :cfg.vocab_size]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **ZAMBA2_TOL)


@pytest.mark.parametrize("remat", ["none", "block"])
def test_zamba2_loss_and_grads_match_reference(remat):
    """The loss and every leaf's gradient, with the scan's checkpointed
    shared block under both remat settings.  Gradients are compared by
    each leaf's relative L2 gap: 1e-4 is a hundred times what summation
    order gives here."""
    ref = _zamba2_reference()
    cfg, m = _zamba2_tiny()
    params, _ = split(tfm.init(KEY, cfg))
    toks = jax.random.randint(KEY, (2, 33), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    pcfg = ParallelConfig(remat=remat)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, batch, cfg, pcfg)[0]))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, m)))(params)
    # a mean of float32 cross-entropies near ln(256): summation order only
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(want_grads)):
        gap = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("change", ["swap_blocks", "zero_adapter"])
def test_zamba2_application_index_matters(change):
    """A wrong application index cannot pass the comparisons above:
    swapping blocks A and B, or zeroing the middle application's adapter
    (block B's only one), moves the logits far beyond their tolerance."""
    cfg, _ = _zamba2_tiny()
    params, _ = split(tfm.init(KEY, cfg))
    toks = jax.random.randint(KEY, (2, 24), 0, cfg.vocab_size)
    altered = dict(params)
    if change == "swap_blocks":
        altered["shared_blocks"] = jax.tree.map(lambda a: a[::-1],
                                                params["shared_blocks"])
    else:
        altered["adapter"] = jax.tree.map(lambda a: a.at[1].set(0.0),
                                          params["adapter"])
    fwd = jax.jit(lambda p: _zamba2_logits(p, cfg, toks))
    gap = float(jnp.max(jnp.abs(fwd(altered) - fwd(params))))
    assert gap > 100 * ZAMBA2_TOL["atol"], gap


def test_zamba2_engine_serves_reference_greedy_tokens():
    """``serve.Engine`` (its jitted prefill and decode_step) serves the
    tokens the reference's full forward ranks first at every step."""
    from repro.serve.engine import Engine, EngineConfig, Request
    ref = _zamba2_reference()
    cfg, m = _zamba2_tiny()
    params, _ = split(tfm.init(KEY, cfg))
    eng = Engine(params, cfg, ecfg=EngineConfig(max_batch=2, cache_len=32))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    with jax.default_matmul_precision("highest"):
        eng.run_batch(reqs)
        for r in reqs:
            seq = jnp.asarray([r.prompt + r.output[:-1]], jnp.int32)
            logits = ref.forward(params, seq, m, positions=np.arange(
                len(r.prompt) - 1, seq.shape[1]))[0]
            assert r.output == [int(t) for t in jnp.argmax(logits, -1)]
