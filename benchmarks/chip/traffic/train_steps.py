"""Training steps through the step that ``train.Trainer`` builds
(``parallel.steps.make_train_setup``), fed by ``train.data.PrefetchIterator``.

Parameters (the cell file's ``params``): ``seq_len``, ``global_batch``,
the ("data", "model") ``mesh``, ``remat``, and ``optim`` (the
``OptimConfig`` the program runs and the reference copies).  Rows are
uniform token ids drawn from (seed, step): every row differs.

Set-up builds one object, the compiled step with its state (weights from
the seed, Adam's state as the program initialises it), and drives it
through its first ``check_steps`` steps with the window's own call and
feed; the window then goes on with that same state.  Each step is one
dispatch and one ``block_until_ready`` of the loss, as ``Trainer.run``
makes it.  (``Trainer.run`` itself ends in a synchronous save of the
whole state, which is why the window drives its step and not it.)

The check follows those first steps with the plain reference: each
step's loss, the norm of the first gradient as the optimizer got it (read
from Adam's first moment after one step), and the norm of each weight's
change after them (read from the float32 master copy), both by the worst
leaf.
"""

from __future__ import annotations

import math
import tempfile

import numpy as np

from compare import leaf_gap, moved_leaves
from harness import Check


class Source:
    """The batches: ``batch(step)`` is a pure function of (seed, step)."""

    def __init__(self, seed: int, vocab: int, seq: int, batch: int):
        self.seed, self.vocab, self.seq, self.rows = seed, vocab, seq, batch

    def batch(self, step: int):
        rng = np.random.default_rng((self.seed, step))
        t = rng.integers(0, self.vocab, (self.rows, self.seq + 1),
                         dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def leaf_norms(tree, scale=1.0):
    """{leaf path: float32 L2 norm x scale}, computed on the device."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) * scale
            for (p, _), n in zip(flat, norms)}


def diff_norms(a, b):
    import jax
    import jax.numpy as jnp
    return leaf_norms(jax.jit(lambda x, y: jax.tree.map(
        lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32), x, y))(
            a, b))


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell["params"]
        self.losses = []
        self._ref = None

    def setup(self):
        import jax
        from repro.launch.mesh import make_mesh
        from repro.models.config import ParallelConfig, ShapeConfig
        from repro.parallel.steps import TrainState
        from repro.train.data import PrefetchIterator
        from repro.train.optim import OptimConfig, init_adam
        from repro.train.train_loop import Trainer, TrainerConfig

        ctx, p = self.ctx, self.p
        self.ocfg = OptimConfig(**p["optim"])
        self.mesh = make_mesh(tuple(p["mesh"]), ("data", "model"),
                              devices=ctx.devices)
        shape = ShapeConfig(ctx.name, "train", p["seq_len"],
                            p["global_batch"])
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench_")
        self.trainer = Trainer(ctx.cfg, shape, self.mesh,
                               ParallelConfig(remat=p["remat"]), self.ocfg,
                               TrainerConfig(seed=ctx.seed_31,
                                             checkpoint_dir=self._tmp.name))
        su = self.trainer.setup
        self.step_fn = su.step_fn
        self.param_shardings = su.param_shardings
        ocfg = self.ocfg
        # the weights as the reference gets them (one compiled program
        # draws both), and Adam's state as the program initialises it
        with self.mesh:
            self.state = jax.jit(
                lambda p: TrainState(params=p, opt=init_adam(p, ocfg)),
                out_shardings=su.state_shardings, donate_argnums=0)(
                    ctx.weights(su.param_shardings))
        self.source = Source(ctx.seed, ctx.model["vocab_size"],
                             p["seq_len"], p["global_batch"])
        self.it = PrefetchIterator(self.source)
        self.tokens_per_step = p["seq_len"] * p["global_batch"]

        self.first_losses = []
        for n in range(p["check_steps"]):
            self.first_losses.append(float(self._step()))
            if n == 0:
                self.first_grad = leaf_norms(self.state.opt.m,
                                             1.0 / (1.0 - ocfg.b1))
        self.first_change = diff_norms(self.state.opt.master,
                                       ctx.weights(su.param_shardings))

    def _step(self):
        import jax
        rec = self.ctx.rec
        with rec.span("bench.next_batch"):
            batch = next(self.it)
        with rec.span("bench.train_step"), self.mesh:
            self.state, metrics = self.step_fn(self.state, batch)
            jax.block_until_ready(metrics["loss"])
        return metrics["loss"]

    def window(self, seconds: float) -> dict:
        rec = self.ctx.rec
        rec.open_window()
        while True:
            self.losses.append(self._step())
            rec.tick()
            if rec.elapsed() >= seconds:
                break
        rec.close_window()
        rec.counters["steps"] = len(self.losses)
        return {"train_tokens_per_s":
                len(self.losses) * self.tokens_per_step / rec.window_s}

    @property
    def attempted(self) -> int:
        return len(self.losses)

    @property
    def failed(self) -> int:
        return sum(not math.isfinite(float(x)) for x in self.losses)

    def free(self):
        self.it.close()
        del self.state, self.step_fn, self.trainer
        self._tmp.cleanup()

    # -- the reference ---------------------------------------------------
    def reference_readings(self, rounding: str) -> dict:
        """Losses, first clipped gradient norms and change norms of the
        reference over the same first steps."""
        import jax
        import jax.numpy as jnp
        ctx, o = self.ctx, self.ocfg
        rnd = ctx.reference.ROUNDING[rounding]

        def lr_at(t):
            warm = min(t / max(o.warmup_steps, 1), 1.0)
            prog = min(max((t - o.warmup_steps) /
                           max(o.total_steps - o.warmup_steps, 1), 0.0), 1.0)
            cos = 0.5 * (1 + math.cos(math.pi * prog))
            return o.lr * warm * (o.min_lr_ratio + (1 - o.min_lr_ratio) * cos)

        def step(w, mo, vo, batch, t, lr):
            loss, g = jax.value_and_grad(
                lambda w: ctx.reference.loss(w, batch, ctx.model, rnd))(w)
            gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            clip = jnp.minimum(1.0, o.grad_clip / jnp.maximum(gnorm, 1e-12))
            g = jax.tree.map(lambda x: x * clip, g)
            mo = jax.tree.map(lambda m, x: o.b1 * m + (1 - o.b1) * x, mo, g)
            vo = jax.tree.map(lambda v, x: o.b2 * v + (1 - o.b2) * x * x,
                              vo, g)
            bc1, bc2 = 1 - o.b1 ** t, 1 - o.b2 ** t
            w = jax.tree.map(
                lambda w, m, v: w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) +
                                                       o.eps)
                                          + o.weight_decay * w), w, mo, vo)
            return w, mo, vo, loss

        w = ctx.reference_weights(jnp.float32, self.param_shardings)
        mo = jax.tree.map(jnp.zeros_like, w)
        vo = jax.tree.map(jnp.zeros_like, w)
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        losses = []
        with jax.default_matmul_precision("highest"):
            for t in range(1, self.p["check_steps"] + 1):
                batch = {k: jnp.asarray(v)
                         for k, v in self.source.batch(t - 1).items()}
                w, mo, vo, loss = jstep(w, mo, vo, batch, float(t),
                                        lr_at(t))
                losses.append(float(loss))
                if t == 1:
                    grad = leaf_norms(mo, 1.0 / (1.0 - o.b1))
        del mo, vo
        change = diff_norms(w, ctx.reference_weights(jnp.float32,
                                                     self.param_shardings))
        return {"losses": losses, "grad": grad, "change": change}

    def _numbers(self, ref, prog):
        keep = moved_leaves(ref["grad"])
        return {
            "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                       ref["losses"])),
            "grad_gap": leaf_gap(prog["grad"], ref["grad"])[0],
            "change_gap": leaf_gap(prog["change"], ref["change"], keep)[0],
        }

    def program_readings(self) -> dict:
        return {"losses": self.first_losses, "grad": self.first_grad,
                "change": self.first_change}

    def _checks(self, readings):
        if self._ref is None:
            self._ref = self.reference_readings("exact")
        lim = self.ctx.cell["limits"]
        return [Check(k, v, lim[k])
                for k, v in self._numbers(self._ref, readings).items()]

    def check(self):
        return self._checks(self.program_readings())

    def control(self):
        """The same numbers for an fp8 reference in the program's place."""
        return self._checks(self.reference_readings("fp8"))
