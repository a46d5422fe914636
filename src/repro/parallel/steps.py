"""jit-compiled train / prefill / decode steps with full sharding metadata.

``make_setup`` assembles everything the launcher and the dry-run need for an
(arch × shape × mesh) cell *without allocating anything*: parameter /
optimizer / decode-state shapes via ``jax.eval_shape`` and their
``NamedSharding``s via the Ruleset, plus the jitted step function with
``in_shardings`` / ``out_shardings`` / donation wired up.

This is the module the multi-pod dry-run lowers (deliverable (e)).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.config import ModelConfig, ParallelConfig, ShapeConfig
from repro.models.modules import AxisNames, split
from repro.train.optim import AdamState, OptimConfig, QTensor, adam_update, init_adam
from .sharding import Ruleset


class TrainState(NamedTuple):
    params: Any
    opt: AdamState


# activations, frontend embeddings and the decode state
COMPUTE_DTYPE = jnp.bfloat16


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — the dry-run contract)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, pcfg: ParallelConfig
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one cell, as ShapeDtypeStructs (no allocation).

    Modality frontends are stubs per the task spec: ``patch_embeds`` /
    ``frames`` are precomputed embeddings."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct

    if shape.kind == "decode":
        batch = {"tokens": sd((B, 1), i32)}
        return batch

    batch = {}
    s_text = S
    if cfg.family == "vlm":
        batch["patch_embeds"] = sd((B, cfg.n_patches, cfg.d_model),
                                   COMPUTE_DTYPE)
        s_text = S - cfg.n_patches
    if cfg.family == "audio":
        batch["frames"] = sd((B, cfg.enc_seq, cfg.d_model), COMPUTE_DTYPE)
    batch["tokens"] = sd((B, s_text), i32)
    if shape.kind == "train":
        batch["labels"] = sd((B, s_text), i32)
    return batch


def batch_shardings(cfg, shape, ruleset: Ruleset):
    b = ruleset.batch_axes(shape.global_batch)
    mesh = ruleset.mesh
    ns = lambda spec: NamedSharding(mesh, spec)
    out = {}
    for k, v in input_specs(cfg, shape, ruleset.pcfg).items():
        out[k] = ns(P(b, None, None)) if k in ("patch_embeds", "frames") \
            else ns(P(b, None))
    return out


# --------------------------------------------------------------------------
# setup
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CellSetup:
    """Everything needed to lower/compile/run one (arch × shape × mesh)."""
    cfg: ModelConfig
    pcfg: ParallelConfig
    shape: ShapeConfig
    mesh: Mesh
    ruleset: Ruleset
    param_shapes: Any
    param_shardings: Any
    step_fn: Any                 # jitted
    example_args: Tuple          # ShapeDtypeStructs to pass to .lower()
    state_shapes: Any = None     # TrainState / DecodeState shapes
    state_shardings: Any = None


def _enc_fn(cfg, pcfg, constrain, enc_layer_constrain=lambda bp: bp):
    if cfg.family != "audio":
        return None
    from repro.models.whisper import encode
    return lambda p, b: encode(p, b, cfg, pcfg, constrain,
                               layer_constrain=enc_layer_constrain)


def make_layer_constrain(ruleset: Ruleset, axes_blocks):
    """Constrain a per-layer parameter slice to its stored sharding (with
    the leading 'layers' axis dropped) — keeps FSDP gathers inside the layer
    loop instead of materializing the gathered full stack."""
    mesh = ruleset.mesh
    is_ax = lambda x: isinstance(x, AxisNames)
    specs = jax.tree.map(
        lambda a: NamedSharding(mesh, ruleset.spec(AxisNames(*tuple(a)[1:]))),
        axes_blocks, is_leaf=is_ax)

    def f(bp):
        return jax.tree.map(jax.lax.with_sharding_constraint, bp, specs)
    return f


def _param_setup(cfg, pcfg, mesh):
    ruleset = Ruleset(mesh, cfg, pcfg)
    pdt = _dtype(pcfg.param_dtype)
    holder = {}

    def f(k):
        vals, axes = split(tfm.init(k, cfg, dtype=pdt))
        holder["axes"] = axes          # static metadata, captured at trace time
        return vals

    param_shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    axes = holder["axes"]
    param_shardings = ruleset.param_shardings(axes)
    return ruleset, param_shapes, axes, param_shardings


def opt_state_shardings(ruleset: Ruleset, axes, ocfg: OptimConfig):
    mesh = ruleset.mesh
    ns = lambda spec: NamedSharding(mesh, spec)
    is_ax = lambda x: isinstance(x, AxisNames)

    def pspec(a):
        return ns(ruleset.opt_spec(a))

    def moment(a):
        if ocfg.moments_dtype == "int8":
            row = ruleset.opt_spec(a)
            scale_spec = P(*tuple(row)[:-1]) if len(a) >= 1 else P()
            return QTensor(q=pspec(a), scale=ns(scale_spec))
        return pspec(a)

    return AdamState(
        step=ns(P()),
        master=jax.tree.map(pspec, axes, is_leaf=is_ax) if ocfg.master else None,
        m=jax.tree.map(moment, axes, is_leaf=is_ax),
        v=jax.tree.map(moment, axes, is_leaf=is_ax),
    )


def make_train_setup(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                     pcfg: Optional[ParallelConfig] = None,
                     ocfg: Optional[OptimConfig] = None) -> CellSetup:
    pcfg = pcfg or ParallelConfig()
    ocfg = ocfg or OptimConfig()
    ruleset, param_shapes, axes, param_shardings = _param_setup(cfg, pcfg, mesh)
    constrain = ruleset.constrain_fn(shape.global_batch)
    lc = make_layer_constrain(ruleset, axes["blocks"])
    enc_lc = (make_layer_constrain(ruleset, axes["encoder"]["blocks"])
              if cfg.family == "audio" else (lambda bp: bp))
    enc_fn = _enc_fn(cfg, pcfg, constrain, enc_lc)

    opt_shapes = jax.eval_shape(lambda p: init_adam(p, ocfg), param_shapes)
    state_shapes = TrainState(params=param_shapes, opt=opt_shapes)
    state_shardings = TrainState(params=param_shardings,
                                 opt=opt_state_shardings(ruleset, axes, ocfg))

    def train_step(state: TrainState, batch):
        def loss_f(params):
            return tfm.loss_fn(params, batch, cfg, pcfg,
                               constrain=constrain, enc_fn=enc_fn,
                               layer_constrain=lc)
        (_, metrics), grads = jax.value_and_grad(loss_f, has_aux=True)(
            state.params)
        new_params, new_opt, om = adam_update(state.params, grads,
                                              state.opt, ocfg)
        metrics = {**metrics, **om}
        return TrainState(new_params, new_opt), metrics

    bshard = batch_shardings(cfg, shape, ruleset)
    metrics_sh = None  # replicated by default
    step = jax.jit(train_step,
                   in_shardings=(state_shardings, bshard),
                   out_shardings=(state_shardings, metrics_sh),
                   donate_argnums=(0,))
    return CellSetup(cfg=cfg, pcfg=pcfg, shape=shape, mesh=mesh,
                     ruleset=ruleset, param_shapes=param_shapes,
                     param_shardings=param_shardings, step_fn=step,
                     example_args=(state_shapes, input_specs(cfg, shape, pcfg)),
                     state_shapes=state_shapes, state_shardings=state_shardings)


def make_prefill_setup(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                       pcfg: Optional[ParallelConfig] = None) -> CellSetup:
    pcfg = (pcfg or ParallelConfig()).replace(remat="none")
    ruleset, param_shapes, axes, param_shardings = _param_setup(cfg, pcfg, mesh)
    constrain = ruleset.constrain_fn(shape.global_batch)
    lc = make_layer_constrain(ruleset, axes["blocks"])
    enc_lc = (make_layer_constrain(ruleset, axes["encoder"]["blocks"])
              if cfg.family == "audio" else (lambda bp: bp))
    enc_fn = _enc_fn(cfg, pcfg, constrain, enc_lc)
    cache_len = shape.seq_len

    def prefill_step(params, batch):
        return tfm.prefill(params, batch, cfg, pcfg, cache_len,
                           constrain=constrain, enc_fn=enc_fn,
                           layer_constrain=lc)

    state_shardings = ruleset.decode_state_shardings(cfg, shape.global_batch)
    bshard = batch_shardings(cfg, shape, ruleset)
    b = ruleset.batch_axes(shape.global_batch)
    logits_sh = NamedSharding(mesh, P(b, ruleset.tp))
    step = jax.jit(prefill_step,
                   in_shardings=(param_shardings, bshard),
                   out_shardings=(logits_sh, state_shardings))
    return CellSetup(cfg=cfg, pcfg=pcfg, shape=shape, mesh=mesh,
                     ruleset=ruleset, param_shapes=param_shapes,
                     param_shardings=param_shardings, step_fn=step,
                     example_args=(param_shapes, input_specs(cfg, shape, pcfg)),
                     state_shardings=state_shardings)


def make_decode_setup(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      pcfg: Optional[ParallelConfig] = None) -> CellSetup:
    """serve_step: one new token against a cache of ``shape.seq_len``."""
    pcfg = (pcfg or ParallelConfig()).replace(remat="none")
    ruleset, param_shapes, axes, param_shardings = _param_setup(cfg, pcfg, mesh)
    constrain = ruleset.constrain_fn(shape.global_batch)
    lc = make_layer_constrain(ruleset, axes["blocks"])

    state_shapes = jax.eval_shape(
        lambda: tfm.init_decode_state(cfg, shape.global_batch,
                                      shape.seq_len, COMPUTE_DTYPE))
    state_shardings = ruleset.decode_state_shardings(cfg, shape.global_batch)

    def decode(params, state, tokens):
        return tfm.decode_step(params, tokens, state, cfg, pcfg,
                               constrain=constrain, layer_constrain=lc)

    b = ruleset.batch_axes(shape.global_batch)
    logits_sh = NamedSharding(mesh, P(b, ruleset.tp))
    tok_sh = NamedSharding(mesh, P(b, None))
    step = jax.jit(decode,
                   in_shardings=(param_shardings, state_shardings, tok_sh),
                   out_shardings=(logits_sh, state_shardings),
                   donate_argnums=(1,))
    toks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return CellSetup(cfg=cfg, pcfg=pcfg, shape=shape, mesh=mesh,
                     ruleset=ruleset, param_shapes=param_shapes,
                     param_shardings=param_shardings, step_fn=step,
                     example_args=(param_shapes, state_shapes, toks),
                     state_shapes=state_shapes, state_shardings=state_shardings)


def moe_ep_ffn_fn(ruleset: Ruleset, cfg: ModelConfig,
                  capacity_factor: Optional[float] = None):
    """Bind the explicit shard_map All-to-All expert dispatch to a cell.

    Returns ``f(params_ffn, x) -> (out, aux)`` running
    :func:`repro.models.moe.moe_ffn_ep` on the cell's mesh over the
    Ruleset's active EP axis (``pcfg.moe_ep_axis``).  Raises if the cell
    has no valid EP axis — EP is a decision
    (``StrategyDecision.ep > 1``), not a silent fallback."""
    if not getattr(ruleset, "ep_axis", None):
        raise ValueError(
            "moe_ep_ffn_fn: the cell's ParallelConfig.moe_ep_axis is unset "
            "or invalid for this mesh/model — expert parallelism needs a "
            "data axis whose size divides n_experts")
    from repro.models.moe import moe_ffn_ep

    def f(params_ffn, x):
        return moe_ffn_ep(params_ffn, x, cfg, mesh=ruleset.mesh,
                          ep_axis=ruleset.ep_axis,
                          capacity_factor=capacity_factor)
    return f


def make_setup(cfg, shape, mesh, pcfg=None, ocfg=None) -> CellSetup:
    if shape.kind == "train":
        return make_train_setup(cfg, shape, mesh, pcfg, ocfg)
    if shape.kind == "prefill":
        return make_prefill_setup(cfg, shape, mesh, pcfg)
    return make_decode_setup(cfg, shape, mesh, pcfg)
