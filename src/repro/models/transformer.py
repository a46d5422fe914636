"""Decoder-only LM covering the dense / moe / ssm / hybrid / zamba2 / vlm
families.

One model class (functions + pytrees, no framework) serves all ten assigned
architectures.  Layers are *stacked* on a leading ``layers`` axis and
executed with ``lax.scan`` so the compiled HLO is O(1) in depth — essential
for the 512-device dry-run compile times — with ``jax.checkpoint`` (remat)
around the block body.

The ssm, hybrid and zamba2 families run one scan over their stacked
Mamba2 layers with one body.  A shared block (one set of weights, several
applications, each with its own entry of the shared KV cache) runs at the
layers of ``ModelConfig.application_layers``: the scan carries, per layer,
which block runs (none, or block k) and which application it is, and the
body takes it by a ``lax.switch``.  The hybrid's shared attention+MLP
block runs after every ``attn_every``-th layer and updates the hidden
state.  zamba2 (Zyphra's published block) takes block
``j % n_shared_blocks`` before each layer of ``hybrid_layer_ids``; it
reads concat(h, embedding), and its output T enters that Mamba2 layer's
input only: h ← h + Mamba2(RMSNorm(h + L_j·T)), with the small
per-application leaves (the MLP adapter, L_j) indexed by j.

Each block kind runs under one ``jax.named_scope`` (``embed``, ``ssm`` with
``ssd`` inside it, ``attention``, ``mlp``, ``moe``, ``lm_head``, ``loss``;
``optimizer`` in ``train.optim``): the scope is metadata on the compiled
ops, so a profile's device time names its block.  The scan's own slicing
and state updates are left unscoped on purpose, so that what XLA makes of
them shows apart from the blocks.

Entry points:
  * ``init``          — Box-tree of parameters.
  * ``loss_fn``       — (params, batch) → (loss, metrics); full causal LM.
  * ``prefill``       — builds the decode state (KV caches / SSM states).
  * ``decode_step``   — one token for every sequence in the batch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig, ParallelConfig
from .layers import (KVCache, apply_attn_block, apply_zamba2_block,
                     init_attn_block, init_mlp_adapter, init_zamba2_block)
from .modules import (Box, AxisNames, dense_init, embed_init, ones_init,
                      rms_norm, softmax_cross_entropy, split)
from .ssm import SSMState, init_mamba2, init_ssm_state, mamba2_forward


class DecodeState(NamedTuple):
    """Everything carried between decode steps (pytree)."""
    kv: Any            # stacked KVCache or None
    ssm: Any           # stacked SSMState or None
    shared_kv: Any     # hybrid, zamba2: stacked KVCache, one per application
    cross_kv: Any      # enc-dec: stacked static cross-attention cache
    index: jnp.ndarray  # scalar int32 — next write position / #tokens seen


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _stack_init(block_init, keys):
    """vmap an init over layer keys; re-attach 'layers' axis metadata."""
    one = block_init(keys[0])
    _, axes_one = split(one)

    def vinit(k):
        v, _ = split(block_init(k))
        return v

    vals = jax.vmap(vinit)(keys)
    axes = jax.tree.map(lambda a: a.stacked(), axes_one,
                        is_leaf=lambda x: isinstance(x, AxisNames))
    return jax.tree.map(Box, vals, axes,
                        is_leaf=lambda x: isinstance(x, AxisNames))


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    keys = jax.random.split(key, 8)
    V = cfg.padded_vocab
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], V, cfg.d_model, dtype),
        "final_norm": ones_init((cfg.d_model,), ("embed",), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, V),
                                       ("embed", "vocab"), scale=0.02, dtype=dtype)

    lkeys = jax.random.split(keys[2], max(cfg.num_layers, 1))
    ffn = "moe" if cfg.n_experts else "mlp"
    if cfg.family in ("ssm", "hybrid", "zamba2"):
        params["blocks"] = _stack_init(
            lambda k: {"ln": ones_init((cfg.d_model,), ("embed",), dtype),
                       "ssm": init_mamba2(k, cfg, dtype)}, lkeys)
    else:
        with_cross = cfg.family == "audio"
        params["blocks"] = _stack_init(
            lambda k: init_attn_block(k, cfg, dtype, ffn=ffn,
                                      with_cross=with_cross), lkeys)
    if cfg.family == "hybrid":
        params["shared_attn"] = init_attn_block(keys[3], cfg, dtype)
    if cfg.family == "zamba2":
        params["shared_blocks"] = _stack_init(
            lambda k: init_zamba2_block(k, cfg, dtype),
            jax.random.split(keys[3], cfg.n_shared_blocks))
        akeys = jax.random.split(keys[6], cfg.n_applications)
        params["adapter"] = _stack_init(
            lambda k: init_mlp_adapter(k, cfg, dtype), akeys)
        params["shared_out"] = _stack_init(
            lambda k: {"out_proj": dense_init(
                k, (cfg.d_model, cfg.d_model), ("embed", "embed_out"),
                dtype=dtype)}, jax.random.split(keys[7], cfg.n_applications))
    if cfg.family == "vlm":
        params["mm_proj"] = dense_init(keys[4], (cfg.d_model, cfg.d_model),
                                       ("embed", "embed_out"), dtype=dtype)
    if cfg.family == "audio":
        from .whisper import init_encoder
        params["encoder"] = init_encoder(keys[5], cfg, dtype)
    return params


# --------------------------------------------------------------------------
# shared forward machinery
# --------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch, constrain):
    """Token (+ patch) embedding.  Returns (x, positions)."""
    tokens = batch["tokens"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype) @ params["mm_proj"]
            x = jnp.concatenate([pe, x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return constrain(x), positions


def _maybe_remat(fn, pcfg: ParallelConfig):
    """Checkpoint one layer by ``pcfg.remat``.  Under the layer scan the
    shared blocks are recomputed in the backward pass whatever ``remat``
    says (``_scan_blocks``)."""
    if pcfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if pcfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def _shared_branches(params, cfg, pcfg, x, positions, constrain, **kw):
    """The family's shared blocks as the layer scan's branches
    ``(h, skv, j) -> (out, skv)`` for application j, branch 0 running
    none, and where they run: "before" or "after" their layer.

    zamba2: branch 1 + k is block k; it reads concat(h, embedding) and its
    ``out`` is that layer's Mamba2 input h + L_j·T (before).  hybrid:
    branch 1 is the shared attention+MLP block and its ``out`` the new
    hidden state (after).  Other families have no branches."""
    if cfg.family == "zamba2":
        # each shared block sliced once, outside the scan: the switch picks
        # among whole blocks, never a dynamic slice of them
        blocks = [jax.tree.map(lambda a, k=k: a[k], params["shared_blocks"])
                  for k in range(cfg.n_shared_blocks)]

        def block(h, skv, j, k):
            pick = lambda t: jax.lax.dynamic_index_in_dim(t, j, keepdims=False)
            adapter = jax.tree.map(pick, params["adapter"])
            t, skv = apply_zamba2_block(
                blocks[k], adapter, cfg, pcfg, h, x, positions=positions,
                cache=skv, constrain=constrain, cache_slot=j, **kw)
            with jax.named_scope("shared_in"):
                hin = h + t @ pick(params["shared_out"]["out_proj"])
            return constrain(hin), skv
        run = [functools.partial(block, k=k) for k in range(len(blocks))]
        where = "before"
    elif cfg.family == "hybrid":
        def block(h, skv, j):
            h, skv, _, _ = apply_attn_block(
                params["shared_attn"], cfg, pcfg, h, positions=positions,
                cache=skv, constrain=constrain, cache_slot=j, **kw)
            return h, skv
        run, where = [block], "after"
    else:
        return [], None
    return [lambda h, skv, j: (h, skv)] + run, where


def _scan_blocks(params, cfg, pcfg, x, positions, constrain, *,
                 mode="train", kv=None, ssm=None, shared_kv=None,
                 cross_kv=None, enc_out=None, cache_index=None,
                 cache_len=None, layer_constrain=lambda bp: bp):
    """Run the full stacked block stack.  Returns
    (x, new_kv, new_ssm, new_shared_kv, new_cross_kv, aux).

    ``None`` flows through ``lax.scan`` xs/ys as an empty pytree, so modes
    that carry no cache/state (train) pay zero memory for them.
    """
    L = cfg.num_layers
    is_ssm_family = cfg.family in ("ssm", "hybrid", "zamba2")

    def maybe_scan(body, carry, xs, length):
        """lax.scan, or an unrolled python loop when ``scan_layers=False``
        (used by the dry-run's single/double-layer cost probes so that
        ``cost_analysis`` sees every layer)."""
        if pcfg.scan_layers:
            return jax.lax.scan(body, carry, xs)
        ys = []
        for i in range(length):
            xsl = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, xsl)
            ys.append(y)
        # None subtrees pass through tree.map untouched
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys) if ys else None
        return carry, stacked

    if is_ssm_family:
        # one scan over the stacked Mamba2 layers with the shared blocks
        # inside it, so the stacked weights and state are scan operands
        # whole, never sliced by group
        branches, where = _shared_branches(
            params, cfg, pcfg, x, positions, constrain, mode=mode,
            cache_index=cache_index, cache_len=cache_len)
        skv = None
        if branches and mode == "decode":
            skv = shared_kv
        elif branches and mode == "prefill":
            # each application writes its prompt's KV into a zero buffer
            skv = _shared_kv_zeros(cfg, x.shape[0], cache_len or x.shape[1],
                                   x.dtype)
        scan_ssm = ssm if mode == "decode" else None

        def mamba_layer(h, u, bp, st):
            """h + Mamba2(RMSNorm(u)), and the layer's new state (decode
            and prefill).  ``u`` is None for h itself, or zamba2's
            h + L_j·T."""
            u = rms_norm(h if u is None else u, bp["ln"], cfg.norm_eps)
            if mode == "train":
                return constrain(h + mamba2_forward(bp["ssm"], u, cfg)), None
            out, new_st = mamba2_forward(bp["ssm"], u, cfg, state=st,
                                         return_state=True)
            return constrain(h + out), new_st
        layer = _maybe_remat(mamba_layer, pcfg)

        def shared(h, skv, sel, j):
            """Branch ``sel`` at application j: 0 none, 1 + k block k."""
            if isinstance(sel, (int, np.integer)):   # unrolled: a static call
                return branches[sel](h, skv, j)
            return jax.lax.switch(sel, branches, h, skv, j)
        if branches and pcfg.scan_layers:
            # autodiff keeps one residual slot a layer under the scan: what
            # a shared block saved would be stacked num_layers times, zero
            # in every layer it skips.  Keep only its inputs and recompute
            # it in the backward pass.
            shared = jax.checkpoint(
                shared, policy=jax.checkpoint_policies.nothing_saveable)

        def body(carry, xs):
            h, skv = carry
            bp, st, sched = xs
            # re-pin the per-layer slice to its stored sharding so FSDP
            # all-gathers happen inside the loop body, not on the whole stack
            bp = layer_constrain(bp)
            u = None
            if where == "before":
                u, skv = shared(h, skv, *sched)
            h, new_st = layer(h, u, bp, st)
            if where == "after":
                h, skv = shared(h, skv, *sched)
            return (h, skv), new_st

        sched = None
        if branches:
            # per layer: the branch that runs and the application it is
            app = np.full(L, -1, np.int32)
            app[list(cfg.application_layers)] = np.arange(cfg.n_applications)
            sel = np.where(app >= 0, 1 + app % (len(branches) - 1), 0)
            sched = (sel.astype(np.int32), np.maximum(app, 0))
        (x, skv), new_ssm = maybe_scan(body, (x, skv),
                                       (params["blocks"], scan_ssm, sched), L)
        # train carries no state: new_ssm and skv are None there
        return x, None, new_ssm, skv, None, jnp.zeros((), jnp.float32)

    # --- attention families ------------------------------------------------
    has_cross = cfg.family == "audio"

    def body(carry, xs):
        h, = carry
        bp, kvl, xkvl = xs
        bp = layer_constrain(bp)

        def run(h, bp, kvl, xkvl):
            hh, nkv, nxkv, a = apply_attn_block(
                bp, cfg, pcfg, h, positions=positions, mode=mode,
                cache=kvl, cache_index=cache_index, cache_len=cache_len,
                cross_cache=xkvl, enc_out=enc_out, constrain=constrain)
            if mode == "train":
                nkv, nxkv = None, None
            elif mode == "decode":
                nxkv = None   # cross cache is static; avoid re-stacking it
            return hh, nkv, nxkv, a
        run = _maybe_remat(run, pcfg)
        h, nkv, nxkv, a = run(h, bp, kvl, xkvl)
        return (h,), (nkv, nxkv, a)

    scan_kv = kv if mode == "decode" else None
    scan_cross = cross_kv if (has_cross and mode == "decode") else None
    (x,), (new_kv, new_cross, auxs) = maybe_scan(
        body, (x,), (params["blocks"], scan_kv, scan_cross), L)
    aux = jnp.sum(jnp.asarray(auxs))
    want_cache = mode in ("prefill", "decode")
    return (x, new_kv if want_cache else None, None, None,
            new_cross if (has_cross and mode == "prefill") else None, aux)


def _lm_head(params, cfg, x, constrain):
    """Final norm and the vocabulary projection: (B, S, d) → (B, S, V)."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return constrain(x @ head, "logits")


# --------------------------------------------------------------------------
# training loss
# --------------------------------------------------------------------------

def loss_fn(params, batch, cfg: ModelConfig, pcfg: ParallelConfig,
            constrain=lambda t, kind="residual": t, enc_fn=None,
            layer_constrain=lambda bp: bp):
    """Causal LM loss.  batch: tokens (B,S) int32, labels (B,S) int32
    (−1 = masked), plus family-specific extras (patch_embeds / frames)."""
    x, positions = _embed_inputs(params, cfg, batch, constrain)
    enc_out = enc_fn(params, batch) if enc_fn is not None else None
    x, _, _, _, _, aux = _scan_blocks(params, cfg, pcfg, x, positions,
                                      constrain, mode="train", enc_out=enc_out,
                                      layer_constrain=layer_constrain)
    logits = _lm_head(params, cfg, x, constrain)
    labels = batch["labels"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        # image positions don't predict tokens
        P = batch["patch_embeds"].shape[1]
        pad = jnp.full((labels.shape[0], P), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    with jax.named_scope("loss"):
        loss, count = softmax_cross_entropy(logits, labels, cfg.vocab_size)
    total = loss + cfg.router_aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux, "tokens": count}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def _shared_kv_zeros(cfg: ModelConfig, batch: int, cache_len: int,
                     dtype) -> KVCache:
    """The shared blocks' KV cache, one entry per application."""
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    z = jnp.zeros((cfg.n_applications, batch, eff_len, cfg.n_kv_heads,
                   cfg.head_dim), dtype)
    return KVCache(z, z)


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=jnp.bfloat16) -> DecodeState:
    """Allocate the decode state for a given cache length."""
    L = cfg.num_layers
    kv = ssm = shared = cross = None
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    if cfg.family in ("ssm", "hybrid", "zamba2"):
        ssm = jax.vmap(lambda _: init_ssm_state(cfg, batch, dtype))(jnp.arange(L))
        if cfg.family in ("hybrid", "zamba2"):
            shared = _shared_kv_zeros(cfg, batch, cache_len, dtype)
    else:
        z = jnp.zeros((L, batch, eff_len, cfg.n_kv_heads, cfg.head_dim), dtype)
        kv = KVCache(z, z)
        if cfg.family == "audio":
            zc = jnp.zeros((L, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim), dtype)
            cross = KVCache(zc, zc)
    return DecodeState(kv=kv, ssm=ssm, shared_kv=shared, cross_kv=cross,
                       index=jnp.zeros((), jnp.int32))


def prefill(params, batch, cfg, pcfg, cache_len: int,
            constrain=lambda t, kind="residual": t, enc_fn=None,
            layer_constrain=lambda bp: bp) -> Tuple[jnp.ndarray, DecodeState]:
    """Run the prompt; return (last-token logits, DecodeState)."""
    x, positions = _embed_inputs(params, cfg, batch, constrain)
    enc_out = enc_fn(params, batch) if enc_fn is not None else None
    x, kv, ssm, shared, cross, _ = _scan_blocks(
        params, cfg, pcfg, x, positions, constrain, mode="prefill",
        enc_out=enc_out, cache_len=cache_len, layer_constrain=layer_constrain)
    logits = _lm_head(params, cfg, x[:, -1:], constrain)
    state = DecodeState(kv=kv, ssm=ssm, shared_kv=shared, cross_kv=cross,
                        index=jnp.array(batch["tokens"].shape[1] +
                                        (batch.get("patch_embeds").shape[1]
                                         if cfg.family == "vlm" and
                                         "patch_embeds" in batch else 0),
                                        jnp.int32))
    return logits[:, 0], state


def decode_step(params, tokens, state: DecodeState, cfg, pcfg,
                constrain=lambda t, kind="residual": t,
                layer_constrain=lambda bp: bp
                ) -> Tuple[jnp.ndarray, DecodeState]:
    """One decode step.  tokens: (B, 1) int32 → logits (B, V)."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    B = x.shape[0]
    positions = jnp.broadcast_to(state.index[None, None], (B, 1)).astype(jnp.int32)
    x, kv, ssm, shared, cross, _ = _scan_blocks(
        params, cfg, pcfg, x, positions, constrain, mode="decode",
        kv=state.kv, ssm=state.ssm, shared_kv=state.shared_kv,
        cross_kv=state.cross_kv, cache_index=state.index,
        layer_constrain=layer_constrain)
    logits = _lm_head(params, cfg, x, constrain)
    new_state = DecodeState(kv=kv if kv is not None else state.kv,
                            ssm=ssm if ssm is not None else state.ssm,
                            shared_kv=shared if shared is not None else state.shared_kv,
                            cross_kv=state.cross_kv,
                            index=state.index + 1)
    return logits[:, 0], new_state
