"""Minimal pure-JAX module substrate.

No flax/haiku here — parameters are plain pytrees of ``jnp.ndarray``.  Each
``init_*`` function returns a pytree whose leaves are :class:`Box` — an array
together with its *logical axis names*.  ``split`` separates the value tree
from the axis tree; the axis tree is consumed by ``repro.parallel.sharding``
to produce ``NamedSharding``s for any mesh, which keeps parameter structure
and sharding metadata impossible to de-synchronize.

Logical axis vocabulary (mapped to physical mesh axes by sharding rules):

  ``layers``   stacked-layer leading dim (never sharded; scanned over)
  ``embed``    d_model                                   (FSDP candidate)
  ``qkv``      fused attention projection output         (TP)
  ``heads``    attention heads                           (TP)
  ``kv``       kv heads / kv projection output           (TP when divisible)
  ``mlp``      FFN hidden                                (TP)
  ``vocab``    (padded) vocabulary                       (TP)
  ``expert``   MoE expert dim                            (EP/TP)
  ``ssm_in``   SSM inner channels                        (TP)
  ``null``     never sharded
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AxisNames:
    """Logical axis names for one parameter — deliberately NOT a pytree,
    so an axes-tree has exactly the structure of its value-tree."""

    __slots__ = ("names",)

    def __init__(self, *names: str):
        self.names = tuple(names)

    def stacked(self, name: str = "layers") -> "AxisNames":
        return AxisNames(name, *self.names)

    def __iter__(self):
        return iter(self.names)

    def __len__(self):
        return len(self.names)

    def __eq__(self, other):
        return isinstance(other, AxisNames) and self.names == other.names

    def __hash__(self):
        return hash(self.names)

    def __repr__(self):
        return f"AxisNames{self.names}"


class Box(NamedTuple):
    """A parameter leaf: array value + logical axis names (one per dim)."""

    value: Any
    axes: AxisNames


def is_box(x) -> bool:
    return isinstance(x, Box)


def boxed_tree_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_box)


def split(tree):
    """Split a Box-tree into (value_tree, axes_tree)."""
    values = boxed_tree_map(lambda b: b.value, tree)
    axes = boxed_tree_map(lambda b: b.axes, tree)
    return values, axes


def unsplit(values, axes):
    return jax.tree.map(Box, values, axes,
                        is_leaf=lambda x: isinstance(x, AxisNames))


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, axes, scale: float | None = None, dtype=jnp.float32) -> Box:
    """Truncated-normal fan-in init (the usual transformer default)."""
    fan_in = shape[0] if len(shape) <= 2 else int(math.prod(shape[:-1]))
    std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    v = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    return Box(v, AxisNames(*axes))


def zeros_init(shape, axes, dtype=jnp.float32) -> Box:
    return Box(jnp.zeros(shape, dtype), AxisNames(*axes))


def ones_init(shape, axes, dtype=jnp.float32) -> Box:
    return Box(jnp.ones(shape, dtype), AxisNames(*axes))


def embed_init(key, vocab, d, dtype=jnp.float32) -> Box:
    v = jax.random.normal(key, (vocab, d), dtype) * 0.02
    return Box(v, AxisNames("vocab", "embed"))


# --------------------------------------------------------------------------
# core ops
# --------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-5):
    """RMSNorm in fp32 accumulation (returns x.dtype)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x):
    """Exact (erf) GeLU, as PyTorch's default and Zamba2's ``gelu``."""
    return jax.nn.gelu(x.astype(jnp.float32),
                       approximate=False).astype(x.dtype)


def linear(x, w, b=None):
    """x @ w with optional bias; w may be (d_in, d_out) or (d_in, h, hd)."""
    y = jnp.einsum("...d,dk->...k", x, w.reshape(w.shape[0], -1))
    y = y.reshape(*x.shape[:-1], *w.shape[1:])
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


NEG_BIG = -3e38  # near-min float32; representable in bf16 too


def softmax_cross_entropy(logits, labels, vocab_size: int, z_weight: float = 0.0):
    """Token-level CE over a (possibly padded) vocab; labels < 0 are masked.

    Memory-lean by construction: logits stay in their compute dtype (bf16);
    all fp32 appears only inside reductions (max / exp-sum / einsum with
    ``preferred_element_type``) which XLA fuses — no fp32 (B,S,V) tensor is
    ever materialized.  Padded vocab entries are suppressed with a
    multiplicative mask *inside* the exp-sum so no masked copy of the
    logits is created either.  Vocab may be sharded over TP; the reductions
    become partial + tiny (B,S) all-reduces.
    Returns (mean_loss, token_count).
    """
    v = logits.shape[-1]
    valid_v = None
    if vocab_size < v:
        valid_v = (jnp.arange(v) < vocab_size)
    # stable logsumexp with fused fp32 accumulation
    neg = jnp.asarray(NEG_BIG, logits.dtype)
    masked = logits if valid_v is None else jnp.where(valid_v, logits, neg)
    m = jnp.max(masked.astype(jnp.float32), axis=-1)
    e = jnp.exp(masked.astype(jnp.float32) - m[..., None])
    lse = m + jnp.log(jnp.sum(e, axis=-1))
    label_onehot = jax.nn.one_hot(jnp.maximum(labels, 0), v, dtype=logits.dtype)
    picked = jnp.einsum("...v,...v->...", logits, label_onehot,
                        preferred_element_type=jnp.float32)
    nll = lse - picked
    if z_weight:
        nll = nll + z_weight * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    total = jnp.sum(nll * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return total / count, count
