"""Pipeline parallelism: GPipe schedule over a ``pipe`` mesh axis.

FRED's Sec. II-C PP pattern — boundary activations forwarded stage-to-stage
— maps to ``collective_permute`` on the TPU torus (neighbouring stages on
neighbouring chips under the FRED-style placement in ``launch.mesh``).

Implementation: ``shard_map`` over ``pipe``; each shard holds its stage's
layer stack; a ``lax.scan`` over M + S − 1 ticks shifts microbatch
activations through stages with ``ppermute``.  The bubble, schedule, and
transfer pattern are exactly GPipe [16]; backward differentiates through
the scan (ppermute transposes to the reverse permutation), so one
``jax.grad`` gives pipeline-parallel training.

This module powers examples/tests (2–8 host devices); the 40-cell dry-run
uses DP×TP meshes per the task spec.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_fn(stage_fn: Callable, n_stages: int, n_microbatches: int,
                mesh: Mesh, pipe_axis: str = "pipe"):
    """Build a pipelined apply: (stage_params_stacked, x_mb) → y_mb.

    stage_fn(params_slice, x) → y, applied by each stage to each
    microbatch.  ``stage_params_stacked`` leaves have leading dim
    n_stages (sharded over ``pipe``); ``x_mb`` has leading dim
    n_microbatches (replicated).
    """
    S, M = n_stages, n_microbatches
    idx = jax.lax.axis_index

    def sharded(params, x_mb):
        # params: leaves (1, ...) local stage slice; x_mb: (M, B, ...)
        local = jax.tree.map(lambda a: a[0], params)
        stage = idx(pipe_axis)
        T = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        # initial carries are logically per-stage (varying over pipe)
        buf = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (pipe_axis,),
                            to="varying")
        outs0 = jax.lax.pcast(jnp.zeros((M,) + x_mb.shape[1:], x_mb.dtype),
                              (pipe_axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            mb_in = x_mb[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(stage == 0, mb_in, buf)
            active = (t - stage >= 0) & (t - stage < M)
            y = stage_fn(local, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage emits microbatch (t - S + 1)
            out_idx = jnp.clip(t - S + 1, 0, M - 1)
            emit = (stage == S - 1) & active
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, outs[out_idx]), out_idx, 0)
            # shift activations to the next stage
            buf = jax.lax.ppermute(y, pipe_axis, perm)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs0), jnp.arange(T))
        return outs[None]                     # (1, M, ...) per stage

    mapped = jax.shard_map(sharded, mesh=mesh,
                           in_specs=(P(pipe_axis), P()),
                           out_specs=P(pipe_axis))

    def apply(params_stacked, x_mb):
        stacked = mapped(params_stacked, x_mb)   # (S, M, ...)
        return stacked[-1]                       # only the last stage is real
    return apply


def sequential_reference(stage_fn, params_stacked, x_mb, n_stages: int):
    """Oracle: run stages sequentially on every microbatch."""
    def run_one(x):
        h = x
        for s in range(n_stages):
            ps = jax.tree.map(lambda a: a[s], params_stacked)
            h = stage_fn(ps, h)
        return h
    return jax.vmap(run_one)(x_mb)
