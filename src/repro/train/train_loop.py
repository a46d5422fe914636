"""Production training loop: step timing, metrics, checkpoints, restart.

``Trainer`` wires together the cell setup (model + shardings + jitted
step), the data pipeline, the async checkpointer and the metrics log, and
implements the fault-tolerance contract:

  * auto-resume from the latest committed checkpoint (params, optimizer,
    data-pipeline state, step counter);
  * SIGTERM/SIGINT → synchronous final checkpoint before exit (preemption
    safety);
  * per-step wall-time and token-throughput accounting;
  * straggler hook: a callback observing per-step durations; the default
    policy logs p50/p95 and flags steps > ``straggler_factor``×p50 (on a
    real multi-host deployment this feeds the controller that re-shards
    around slow hosts — single-controller CPU runs only observe).
"""

from __future__ import annotations

import dataclasses
import signal
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.models.config import ModelConfig, ParallelConfig, ShapeConfig
from repro.parallel.steps import CellSetup, TrainState, make_train_setup
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, PrefetchIterator, SyntheticLM
from repro.train.optim import OptimConfig, init_adam
from repro.models.modules import split
from repro.models import transformer as tfm


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3
    seed: int = 0
    straggler_factor: float = 2.0


class Trainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeConfig, mesh,
                 pcfg: Optional[ParallelConfig] = None,
                 ocfg: Optional[OptimConfig] = None,
                 tcfg: Optional[TrainerConfig] = None):
        self.tcfg = tcfg or TrainerConfig()
        self.setup: CellSetup = make_train_setup(cfg, shape, mesh, pcfg, ocfg)
        self.mesh = mesh
        self.cfg = cfg
        self.shape = shape
        self.ocfg = ocfg or OptimConfig()
        self.data = SyntheticLM(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
            global_batch=shape.global_batch, seed=self.tcfg.seed))
        self.ckpt = ckpt.AsyncCheckpointer(self.tcfg.checkpoint_dir,
                                           keep=self.tcfg.keep_checkpoints)
        self.step = 0
        self.history: list[Dict[str, float]] = []
        self._durations: list[float] = []
        self._stop = False

    # ---- state ------------------------------------------------------------
    def init_state(self) -> TrainState:
        pdt = {"bfloat16": jax.numpy.bfloat16,
               "float32": jax.numpy.float32}[self.setup.pcfg.param_dtype]

        def make(key):
            params, _ = split(tfm.init(key, self.cfg, dtype=pdt))
            return TrainState(params=params,
                              opt=init_adam(params, self.ocfg))

        with self.mesh:
            return jax.jit(make, out_shardings=self.setup.state_shardings)(
                jax.random.PRNGKey(self.tcfg.seed))

    def resume_or_init(self) -> TrainState:
        latest = ckpt.latest_step(self.tcfg.checkpoint_dir)
        state = self.init_state()
        if latest is not None:
            state, extras = ckpt.restore(
                self.tcfg.checkpoint_dir, state,
                shardings=self.setup.state_shardings)
            self.step = int(extras.get("step", latest))
            print(f"[trainer] resumed from step {self.step}")
        return state

    # ---- loop ---------------------------------------------------------------
    def run(self, state: Optional[TrainState] = None) -> TrainState:
        t = self.tcfg
        state = state if state is not None else self.resume_or_init()
        it = PrefetchIterator(self.data, start_step=self.step)

        orig_handlers = {}

        def on_signal(signum, frame):
            self._stop = True
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                orig_handlers[sig] = signal.signal(sig, on_signal)
            except ValueError:
                pass  # non-main thread (tests)

        tokens_per_step = self.shape.global_batch * self.shape.seq_len
        try:
            with self.mesh:
                while self.step < t.steps and not self._stop:
                    batch = next(it)
                    t0 = time.perf_counter()
                    state, metrics = self.setup.step_fn(state, batch)
                    jax.block_until_ready(metrics["loss"])
                    dt = time.perf_counter() - t0
                    self.step += 1
                    self._durations.append(dt)
                    self._observe_stragglers()
                    if self.step % t.log_every == 0 or self.step == t.steps:
                        row = {k: float(v) for k, v in metrics.items()}
                        row.update(step=self.step, seconds=dt,
                                   tokens_per_s=tokens_per_step / dt)
                        self.history.append(row)
                        print(f"[trainer] step {self.step} "
                              f"loss={row['loss']:.4f} "
                              f"{row['tokens_per_s']:.0f} tok/s")
                    if self.step % t.checkpoint_every == 0:
                        self.ckpt.save(state, step=self.step,
                                       extras={"step": self.step,
                                               "data": it.state()})
            # final (synchronous) checkpoint — incl. preemption path
            self.ckpt.wait()
            ckpt.save(t.checkpoint_dir, state, step=self.step,
                      extras={"step": self.step, "data": it.state()})
        finally:
            it.close()
            for sig, h in orig_handlers.items():
                signal.signal(sig, h)
        return state

    def _observe_stragglers(self):
        if len(self._durations) < 10:
            return
        recent = np.array(self._durations[-50:])
        p50 = float(np.percentile(recent, 50))
        if self._durations[-1] > self.tcfg.straggler_factor * p50:
            print(f"[trainer] straggler step {self.step}: "
                  f"{self._durations[-1]:.3f}s vs p50 {p50:.3f}s")
