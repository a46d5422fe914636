"""CPU rehearsal of the four-chip cell ``zamba2.train-2x2`` (Zyphra's
published Zamba2 block, ``zamba2-7b-2p``): the cell end to end through
``run.main`` on 4 virtual CPU devices, on its (2, 2) FSDP+TP mesh, at a
tiny size; the fp8 control fails the same checks; the check fails where
either cross-chip fault is planted (one data shard's row left out, the
gradient exchange over "data" left out); and ``work.zamba2``'s forward
FLOPs lie at or below XLA's count of the same program."""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from pathlib import Path

from chipbench_util import BENCH, REPO

CELL = "zamba2.train-2x2"
CONFIG = "zamba2-7b-2p"
# widths a CPU test can hold; 6 layers with hybrid ids 1, 3 and 5, so
# block A runs twice and block B once; MHA as published
TINY_MODEL = dict(num_layers=6, hybrid_layer_ids=[1, 3, 5], d_model=64,
                  n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                  vocab_size=256, ssm_state=16, ssm_headdim=16,
                  adapter_rank=4)
TINY_PARAMS = dict(seq_len=64, global_batch=2)
# limits at the tiny size, between the program's readings and the fp8
# control's there (seeds 5 and 3000000001, CPU, 4 devices): the program
# reads loss_gap 0.0003-0.0007, grad_gap 0.018-0.025 and change_gap
# 0.0024-0.0028; the control 0.0072-0.0089, 0.107-0.176 and 0.012-0.016
TINY_LIMITS = {"loss_gap": 0.002, "grad_gap": 0.06, "change_gap": 0.006}


def _shrink(checkout):
    path = checkout.bench_dir / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model"].update(TINY_MODEL)
    path.write_text(json.dumps(cfg))
    path = checkout.bench_dir / "cells" / f"{CELL}.json"
    cell = json.loads(path.read_text())
    cell["params"].update(TINY_PARAMS)
    cell["limits"] = TINY_LIMITS
    path.write_text(json.dumps(cell))


def _on_4_devices(checkout, code: str) -> str:
    """``code`` in a child process that sees 4 CPU devices, with the
    checkout's ``where`` as ``WHERE``; returns its stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(BENCH), str(Path(__file__).parent),
         env.get("PYTHONPATH", "")])
    where = {k: str(v) if not isinstance(v, bool) else v
             for k, v in checkout.where().items()}
    head = ("from pathlib import Path\n"
            f"WHERE = {where!r}\n"
            "WHERE = {k: Path(v) if isinstance(v, str) else v "
            "for k, v in WHERE.items()}\n")
    proc = subprocess.run([sys.executable, "-c",
                           head + textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@contextlib.contextmanager
def row_left_out():
    """Plant: one data shard's row left out.  Every row of the batch the
    program gets is row 0, so its loss and gradient are row 0's alone;
    the step program itself is unchanged."""
    from repro.train import data
    orig = data.PrefetchIterator

    class RowZero:
        def __init__(self, source):
            self.it = orig(source)

        def __next__(self):
            return {k: v[[0] * v.shape[0]] for k, v in next(self.it).items()}

        def close(self):
            self.it.close()
    data.PrefetchIterator = RowZero
    try:
        yield
    finally:
        data.PrefetchIterator = orig


@contextlib.contextmanager
def exchange_left_out():
    """Plant: the gradient exchange over "data" left out.  Each data
    shard's part of every leaf sharded over "data" is the gradient of
    that shard's own rows alone (a leaf replicated over "data" takes the
    first shard's); the loss reported is still the whole batch's."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.parallel import steps
    from repro.train import train_loop
    from repro.train.optim import adam_update
    orig = train_loop.make_train_setup

    def setup(cfg, shape, mesh, pcfg, ocfg):
        su = orig(cfg, shape, mesh, pcfg, ocfg)
        _, _, axes, _ = steps._param_setup(cfg, su.pcfg, mesh)
        constrain = su.ruleset.constrain_fn(shape.global_batch)
        lc = steps.make_layer_constrain(su.ruleset, axes["blocks"])
        n = mesh.shape["data"]

        def data_dim(sh):
            for i, e in enumerate(sh.spec):
                names = e if isinstance(e, tuple) else (e,)
                if "data" in names:
                    assert names[0] == "data", sh.spec
                    return i
            return None
        dims = jax.tree.map(data_dim, su.param_shardings)
        is_none = lambda x: x is None

        def own(i, g, d):
            if i is None:
                return g if d == 0 else None
            k = g.shape[i] // n
            return jax.lax.slice_in_dim(g, d * k, (d + 1) * k, axis=i)

        def train_step(state, batch):
            B = batch["tokens"].shape[0]
            per = B // n
            params, parts, losses = state.params, [], []
            for d in range(n):
                rows = jnp.array([d * per + r % per for r in range(B)])
                b = {k: v[rows] for k, v in batch.items()}
                (_, m), g = jax.value_and_grad(
                    lambda p: tfm.loss_fn(p, b, cfg, su.pcfg,
                                          constrain=constrain,
                                          layer_constrain=lc),
                    has_aux=True)(params)
                losses.append(m["loss"])
                parts.append(jax.tree.map(lambda i, x: own(i, x, d), dims,
                                          g, is_leaf=is_none))
                # one shard's backward pass at a time
                params, parts = jax.lax.optimization_barrier((params, parts))
            grads = jax.tree.map(
                lambda i, *ps: ps[0] if i is None
                else jnp.concatenate(ps, axis=i), dims, *parts,
                is_leaf=is_none)
            new_params, new_opt, om = adam_update(state.params, grads,
                                                  state.opt, ocfg)
            metrics = dict(m, loss=sum(losses) / n, **om)
            return steps.TrainState(new_params, new_opt), metrics

        step = jax.jit(train_step,
                       in_shardings=(su.state_shardings, steps.batch_shardings(
                           cfg, shape, su.ruleset)),
                       out_shardings=(su.state_shardings, None),
                       donate_argnums=(0,))
        return dataclasses.replace(su, step_fn=step)
    train_loop.make_train_setup = setup
    try:
        yield
    finally:
        train_loop.make_train_setup = orig


def test_train_2x2_end_to_end_and_control(checkout):
    _shrink(checkout)
    out = _on_4_devices(checkout, """
        import gc, json
        import harness, run
        rc = run.main(["--workload", "zamba2.train-2x2", "--seed",
                       "3000000001", "--seconds", "0.5", "--trace", "0"],
                      **WHERE)
        assert rc == 0
        _, ctx, r = run.prepare("zamba2.train-2x2", 5, **WHERE)
        assert ctx.chips == 4
        r.setup()
        r.window(0.0)
        r.free()
        gc.collect()
        program = r.check()
        control = r.control()
        print(json.dumps({
            "program": harness.checks_line(program),
            "program_correct": harness.correct(program),
            "control": harness.checks_line(control),
            "control_correct": harness.correct(control)}))
    """)
    lines = out.strip().splitlines()
    result, seed5 = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["device"]["count"] == 4
    assert seed5["program_correct"] is True, seed5
    assert seed5["control_correct"] is False, seed5


def test_train_2x2_cross_chip_faults_fail(checkout):
    """Either cross-chip fault makes the cell's check come out false: the
    row left out through the loss and the gradient, the exchange left out
    through the gradient with the loss as it was."""
    _shrink(checkout)
    out = _on_4_devices(checkout, """
        import gc, json
        import harness, run
        import test_chipbench_zamba2_7b as t
        for plant in (t.row_left_out, t.exchange_left_out):
            with plant():
                _, ctx, r = run.prepare("zamba2.train-2x2", 5, **WHERE)
                r.setup()
                r.window(0.0)
                r.free()
            gc.collect()
            checks = r.check()
            print(json.dumps({"check": harness.checks_line(checks),
                              "correct": harness.correct(checks)}))
    """)
    row, exchange = [json.loads(x) for x in out.strip().splitlines()[-2:]]
    over = lambda line, k: line["check"][k]["value"] > line["check"][k]["limit"]
    assert row["correct"] is False and over(row, "loss_gap")
    assert over(row, "grad_gap")
    assert exchange["correct"] is False and over(exchange, "grad_gap")
    assert not over(exchange, "loss_gap")


def test_zamba2_flops_at_or_below_xla():
    """XLA's count of the same forward (layers unrolled so that it sees
    each, both shared blocks and every application) includes what the
    benchmark leaves out, never less."""
    from repro.configs.registry import get_config
    from repro.models import transformer as tfm
    from repro.models.config import ParallelConfig
    from repro.models.modules import split
    from work import zamba2
    cfg = dataclasses.replace(get_config("zamba2-7b"), **dict(
        TINY_MODEL, hybrid_layer_ids=(1, 3, 5)))
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    pcfg = ParallelConfig(scan_layers=False, remat="none")
    params = jax.eval_shape(lambda k: split(tfm.init(k, cfg))[0],
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    fwd = jax.jit(lambda p, t: tfm.loss_fn(p, {"tokens": t, "labels": t},
                                           cfg, pcfg)[0])
    cost = fwd.lower(params, tok).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < zamba2.forward_flops(m, 2, 64) <= cost["flops"]
