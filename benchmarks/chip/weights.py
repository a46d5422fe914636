"""Weights the benchmark makes from ``--seed``, in the layout the program
keeps, on the device, in one jitted call.

The program contributes only the layout (the tree of shapes that its
``init`` would return); every value is drawn here, by the leaf's name,
so the reference can be handed the same weights without taking anything
the program made.  Matrices are fan-in scaled truncated normals; the
residual outputs (``wo``, ``w_down``) are further scaled by
1/sqrt(2 x layers); the query and key projections are drawn ``qk_gain``
times wider, which gives attention logits a spread of about qk_gain²
instead of about 1.  At a spread of 1 attention over thousands of keys is
nearly uniform, and a fault in the attention cache would hardly move the
logits; at 4 each query attends to a few keys, as in trained models.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ONES = {"ln", "ln1", "ln2", "final_norm", "norm_g", "d_skip", "q_norm",
        "k_norm"}
ZEROS = {"conv_b", "bq", "bk", "bv"}
FAN_IN = {"in_proj", "out_proj", "wv", "w_gate", "w_up"}


def _leaf(key, name, sd, init, m):
    shape, f32 = sd.shape, jnp.float32
    tn = lambda std: std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                       f32)
    fan_in = shape[-2] if len(shape) >= 2 else 1
    if name in ONES:
        v = jnp.ones(shape, f32)
    elif name in ZEROS:
        v = jnp.zeros(shape, f32)
    elif name == "a_log":
        v = jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, shape[-1])),
                             shape)
    elif name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(0.1)
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        v = jnp.log(jnp.expm1(dt))
    elif name == "embed":
        v = init["embed_std"] * jax.random.normal(key, shape, f32)
    elif name == "lm_head":
        v = tn(init["embed_std"])
    elif name == "conv_w":
        v = tn(1.0 / math.sqrt(fan_in))
    elif name in ("wq", "wk"):
        v = tn(init["qk_gain"] / math.sqrt(fan_in))
    elif name in ("wo", "w_down"):
        v = tn(1.0 / math.sqrt(fan_in * 2 * m["num_layers"]))
    elif name in FAN_IN:
        v = tn(1.0 / math.sqrt(fan_in))
    else:
        raise KeyError(f"weights: no rule for leaf {name!r}; the program's "
                       "parameter tree has a leaf the benchmark does not know")
    return v.astype(sd.dtype)


def initializer(shapes, init: dict, m: dict):
    """A traceable ``key -> weight tree`` for ``shapes`` (a tree of
    ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), path[-1].key, sd, init, m)
                  for i, (path, sd) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return build


def make(shapes, seed: int, init: dict, m: dict, out_shardings=None):
    """The weights drawn from ``seed``: one jitted call, placed by
    ``out_shardings``."""
    draw = jax.jit(initializer(shapes, init, m), out_shardings=out_shardings)
    return draw(jax.random.PRNGKey(seed))
