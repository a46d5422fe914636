"""AdamW from scratch (no optax), with mixed precision + memory modes.

Modes (``OptimConfig``):

* ``master=True``  — fp32 master copy of the (bf16) params; updates applied
  to the master, params re-cast each step (the standard mixed-precision
  recipe).
* ``master=False`` — params updated in their own dtype with fp32 math
  (saves 4 bytes/param — used by arctic-480b to fit HBM).
* ``moments_dtype`` ∈ {float32, bfloat16, int8} — int8 stores blockless
  *per-row* quantized moments (scale shape = param.shape[:-1]), the 8-bit
  Adam memory trick; scales inherit the row dims' sharding so every
  optimizer-state leaf keeps an exactly-divisible jit input sharding.

All state leaves mirror the parameter tree structure, so the sharding rules
in ``parallel.sharding`` apply leaf-for-leaf (``Ruleset.opt_spec``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

try:                                   # the JAX-free CI core lane imports
    import jax                         # this module only for OptimConfig
    import jax.numpy as jnp            # (via parallel.policy); every
except ImportError:                    # array function below needs jax
    jax = jnp = None


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    master: bool = True
    moments_dtype: str = "float32"   # float32 | bfloat16 | int8


class QTensor(NamedTuple):
    """Per-row int8 quantized tensor (non-negative ⇒ unsigned mapping)."""
    q: jnp.ndarray          # int8, same shape as the original
    scale: jnp.ndarray      # fp32, shape = original.shape[:-1] (or () for 1-d)


class AdamState(NamedTuple):
    step: jnp.ndarray
    master: Any             # fp32 params or None
    m: Any                  # moments (array | QTensor per leaf)
    v: Any


def _quantize(x: jnp.ndarray, signed: bool) -> QTensor:
    # bf16 quantization input: halves the materialized temporary for the
    # amax reduction on multi-GB moment leaves; int8 output precision is
    # unaffected (7 bits << bf16's 8 mantissa bits)
    xh = x.astype(jnp.bfloat16)
    xf = xh.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1) if x.ndim > 1 else jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.round(xf / scale[..., None] if x.ndim > 1 else xf / scale)
    q = jnp.clip(q, -127 if signed else 0, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale.astype(jnp.float32))


def _dequantize(t: QTensor) -> jnp.ndarray:
    s = t.scale[..., None] if t.q.ndim > 1 else t.scale
    return t.q.astype(jnp.float32) * s


def _encode_moment(x, dtype: str, signed: bool):
    if dtype == "int8":
        return _quantize(x, signed)
    return x.astype(jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)


def _decode_moment(x):
    if isinstance(x, QTensor):
        return _dequantize(x)
    return x.astype(jnp.float32)


def init_adam(params, ocfg: OptimConfig) -> AdamState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(
        step=jnp.zeros((), jnp.int32),
        master=(jax.tree.map(lambda p: p.astype(jnp.float32), params)
                if ocfg.master else None),
        m=jax.tree.map(lambda z: _encode_moment(z, ocfg.moments_dtype, True), zeros),
        v=jax.tree.map(lambda z: _encode_moment(z, ocfg.moments_dtype, False), zeros),
    )


def lr_schedule(step, ocfg: OptimConfig):
    """Linear warmup → cosine decay to ``min_lr_ratio``."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(ocfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - ocfg.warmup_steps) /
                    jnp.maximum(ocfg.total_steps - ocfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return ocfg.lr * warm * (ocfg.min_lr_ratio + (1 - ocfg.min_lr_ratio) * cos)


def global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adam_update(params, grads, state: AdamState, ocfg: OptimConfig
                ) -> Tuple[Any, AdamState, dict]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    with jax.named_scope("optimizer"):
        step = state.step + 1
        lr = lr_schedule(step, ocfg)
        gnorm = global_norm(grads)
        clip = jnp.minimum(1.0, ocfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
            if ocfg.grad_clip else 1.0

        b1, b2 = ocfg.b1, ocfg.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        is_q = lambda x: isinstance(x, QTensor)

        def leaf_core(p, g, m, v, mw):
            g = g.astype(jnp.float32) * clip
            mf = _decode_moment(m)
            vf = _decode_moment(v)
            mf = b1 * mf + (1 - b1) * g
            vf = b2 * vf + (1 - b2) * jnp.square(g)
            upd = (mf / bc1) / (jnp.sqrt(vf / bc2) + ocfg.eps)
            base = mw if mw is not None else p.astype(jnp.float32)
            new_master = base - lr * (upd + ocfg.weight_decay * base)
            return (new_master.astype(p.dtype),
                    _encode_moment(mf, ocfg.moments_dtype, True),
                    _encode_moment(vf, ocfg.moments_dtype, False),
                    new_master if mw is not None else None)

        # Huge stacked leaves (MoE expert banks: Gbytes of fp32 intermediates)
        # are updated slice-by-slice over the leading 'layers' dim so the fp32
        # temporaries stay one-layer-sized.
        # disabled: broke XLA aliasing (measured +16GiB)
        SCAN_THRESHOLD = 1 << 62

        def leaf(p, g, m, v, mw):
            if p.size <= SCAN_THRESHOLD or p.ndim < 2:
                return leaf_core(p, g, m, v, mw)
            if mw is None:
                def body(_, xs):
                    np_, nm, nv, _none = leaf_core(*xs, None)
                    return None, (np_, nm, nv)
                _, (np_, nm, nv) = jax.lax.scan(body, None, (p, g, m, v))
                return np_, nm, nv, None
            def body(_, xs):
                return None, leaf_core(*xs)
            _, (np_, nm, nv, nmw) = jax.lax.scan(body, None, (p, g, m, v, mw))
            return np_, nm, nv, nmw

        p_flat, treedef = jax.tree.flatten(params)
        g_flat = treedef.flatten_up_to(grads)
        m_flat = treedef.flatten_up_to(state.m)
        v_flat = treedef.flatten_up_to(state.v)
        mw_flat = (treedef.flatten_up_to(state.master)
                   if state.master is not None else [None] * len(p_flat))
        results = [leaf(p, g, m, v, mw) for p, g, m, v, mw
                   in zip(p_flat, g_flat, m_flat, v_flat, mw_flat)]
        unflat = lambda i: jax.tree.unflatten(treedef, [r[i] for r in results])
        new_state = AdamState(
            step=step,
            master=unflat(3) if state.master is not None else None,
            m=unflat(1), v=unflat(2))
        return unflat(0), new_state, {"grad_norm": gnorm, "lr": lr}
