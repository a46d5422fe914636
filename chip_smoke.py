"""Smoke run of the JAX half on a TPU, through the entry points users call.

    python chip_smoke.py              # phases serve + train, one chip
    python chip_smoke.py --chips 4    # phase mesh only, four chips

* ``serve``: zamba2-2.7b at full width and depth (54 Mamba2 layers, nine
  applications of the shared attention block), bf16 weights from a seed,
  served by ``serve.Engine.run_batch``: 4 greedy requests with 2048-token
  prompts and 32 new tokens each.  The logits of the last decode step
  through the cache are checked against the last-position logits of a
  ``tfm.prefill`` over prompt plus generated tokens.
* ``train``: ``train.Trainer`` on a (1, 1) ("data", "model") mesh, 5 steps
  of zamba2-2.7b at published widths cut in depth to one period, seq 4096,
  global batch 1.  Every loss must be finite and the first one near
  ln(vocab).
* ``mesh`` (``--chips 4``): the same one-period Trainer on a (2, 2) FSDP+TP
  mesh and on a (1, 1) mesh of the first chip, same seed, data and global
  batch, 3 steps each; the losses and gradient norms must agree.

Each phase prints one JSON line of smoke observations (compile seconds,
step times, peak device memory): what one run saw, not benchmark metrics.
The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before any phase and prints no such line.

The persistent compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says
(JAX reads that itself), else ``.jax_cache/`` beside this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.config import ParallelConfig, ShapeConfig  # noqa: E402
from repro.models.modules import split  # noqa: E402
from repro.serve.engine import Engine, EngineConfig, Request  # noqa: E402
from repro.train.train_loop import Trainer, TrainerConfig  # noqa: E402

MODEL = "zamba2-2.7b"

# Decode through the cache and a prefill over the same tokens round
# differently in bf16 (chunked vs recurrent SSD, blocked vs cached
# attention), and with a unit roundoff of 2^-9 the gap grows with depth: a
# 54-layer zamba2 of width 256 gives a relative L2 gap of 4.3e-2 on a CPU.
# Twice that admits the bf16 gap; a path in 8-bit floats (roundoff 2^-4,
# 32x larger) fails, and so does a lost SSM or conv state (gap ~1).
SERVE_REL_L2_TOL = 1e-1

# With random weights the logits of a token have a spread of about
# 0.02 * sqrt(d_model) ~ 1, which lifts the first loss above ln(vocab) by
# about half of that spread squared (~0.5 nats at vocab 32000).  A tenth of
# ln(vocab) admits that and refuses a model whose logits blew up.
FIRST_LOSS_REL_TOL = 0.1

# The two layouts compute the same steps in another order of bf16-rounded
# partial sums; the mean loss over thousands of tokens then agrees to far
# less than a hundredth of a nat, and the global gradient norm, a sum over
# every parameter, to far less than a percent.  A gradient that missed its
# sum over the data axis would be off by tens of percent.
MESH_LOSS_ATOL = 2e-2
MESH_GRAD_NORM_RTOL = 1e-2


class SmokeCheckFailed(RuntimeError):
    """A phase produced a result outside its stated tolerance."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeCheckFailed(msg)


def compile_cache_dir(environ) -> str | None:
    """The directory to set as JAX's persistent compile cache, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then reads it itself)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(ROOT / ".jax_cache")


class CompileClock:
    """Adds up JAX's own compile events: tracing, lowering to MLIR and the
    backend compile (a persistent-cache read, on a hit), and counts the
    persistent cache's hits and misses."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.cache_hits, self.cache_misses

    def since(self, snap: tuple) -> dict:
        s, h, m = snap
        return {"compile_s": self.seconds - s,
                "compile_cache_hits": self.cache_hits - h,
                "compile_cache_misses": self.cache_misses - m}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def serve_phase(cfg, *, batch: int = 4, prompt_len: int = 2048,
                new_tokens: int = 32, cache_len: int = 4096,
                seed: int = 0) -> dict:
    """Serve one batch of equal-length greedy requests through
    ``Engine.run_batch`` and check the last decode step's logits against
    a prefill over the same tokens.  Returns the observations."""
    params = jax.jit(lambda k: split(tfm.init(k, cfg, dtype=jnp.bfloat16))[0]
                     )(jax.random.PRNGKey(seed))
    eng = Engine(params, cfg,
                 ecfg=EngineConfig(max_batch=batch, cache_len=cache_len))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, prompt_len))

    def requests():
        # equal prompt lengths: the Engine left-pads ragged batches without
        # a mask, so only an equal-length batch has a known right answer
        return [Request(uid=i, prompt=p.tolist(), max_new_tokens=new_tokens)
                for i, p in enumerate(prompts)]

    eng.run_batch(requests(), seed=seed)          # compiles both steps
    done = eng.run_batch(requests(), seed=seed)

    ref_prefill = jax.jit(lambda p, t: tfm.prefill(
        p, {"tokens": t}, cfg, eng.pcfg, cache_len)[0])
    # the last decode step consumed every generated token but the last
    context = done[0].prompt + done[0].output[:-1]
    ref = np.asarray(ref_prefill(params, jnp.asarray([context], jnp.int32))
                     [0, :cfg.vocab_size], np.float32)
    got = np.asarray(eng.last_logits[0, :cfg.vocab_size], np.float32)
    rel_l2 = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    _check(bool(np.isfinite(got).all()), "serve: non-finite decode logits")
    _check(all(len(r.output) == new_tokens for r in done),
           "serve: a request stopped short of its new tokens")
    _check(rel_l2 <= SERVE_REL_L2_TOL,
           f"serve: decode-vs-prefill logits rel L2 {rel_l2:.4g} > "
           f"{SERVE_REL_L2_TOL}")

    wall = done[0].latency_s
    return {
        "model": cfg.name, "layers": cfg.num_layers, "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "cache_len": cache_len,
        "prefill_s": eng.prefill_s,
        "decode_step_p50_s": statistics.median(eng.decode_step_s),
        "tokens_per_s": batch * new_tokens / wall,
        "batch_wall_s": wall,
        "check_decode_vs_prefill_rel_l2": rel_l2,
        "check_decode_vs_prefill_max_abs": float(np.max(np.abs(got - ref))),
        "check_tol_rel_l2": SERVE_REL_L2_TOL,
    }


def _train(cfg, shape, mesh, *, steps: int, seed: int) -> list[dict]:
    """Run the Trainer for ``steps`` steps; return its per-step rows.
    Checkpoints (the final one holds the whole optimizer state) go to a
    temporary directory that is removed afterwards."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        tr = Trainer(cfg, shape, mesh, ParallelConfig(remat="block"),
                     tcfg=TrainerConfig(steps=steps, log_every=1,
                                        checkpoint_dir=ckpt_dir, seed=seed))
        tr.run()
    losses = [row["loss"] for row in tr.history]
    _check(len(losses) == steps and all(map(math.isfinite, losses)),
           f"train: expected {steps} finite losses, got {losses}")
    return tr.history


def train_phase(cfg, shape, mesh, *, steps: int = 5, seed: int = 0) -> dict:
    """``steps`` Trainer steps; every loss finite, the first near
    ln(vocab) as a randomly initialised model gives."""
    rows = _train(cfg, shape, mesh, steps=steps, seed=seed)
    losses = [row["loss"] for row in rows]
    ln_v = math.log(cfg.vocab_size)
    _check(abs(losses[0] - ln_v) <= FIRST_LOSS_REL_TOL * ln_v,
           f"train: first loss {losses[0]:.4f} not within "
           f"{FIRST_LOSS_REL_TOL} of ln(vocab) {ln_v:.4f}")
    secs = [row["seconds"] for row in rows]
    return {
        "model": cfg.name, "layers": cfg.num_layers,
        "mesh": dict(mesh.shape), "seq_len": shape.seq_len,
        "global_batch": shape.global_batch, "steps": steps,
        "losses": losses, "ln_vocab": ln_v,
        "step_s": secs,
        # the first step includes the step's compile
        "step_p50_after_first_s": statistics.median(secs[1:] or secs),
    }


def mesh_phase(cfg, shape, *, steps: int = 3, seed: int = 0) -> dict:
    """The Trainer on a (2, 2) FSDP+TP mesh against a (1, 1) mesh of the
    first device: same seed, data and global batch; the losses and the
    global gradient norms must agree step by step."""
    devs = jax.devices()
    axes = ("data", "model")
    wide = _train(cfg, shape, make_mesh((2, 2), axes, devices=devs[:4]),
                  steps=steps, seed=seed)
    one = _train(cfg, shape, make_mesh((1, 1), axes, devices=devs[:1]),
                 steps=steps, seed=seed)
    lw = [row["loss"] for row in wide]
    l1 = [row["loss"] for row in one]
    gw = [row["grad_norm"] for row in wide]
    g1 = [row["grad_norm"] for row in one]
    diff = max(abs(a - b) for a, b in zip(lw, l1))
    gdiff = max(abs(a - b) / b for a, b in zip(gw, g1))
    _check(diff <= MESH_LOSS_ATOL,
           f"mesh: 2x2 losses {lw} vs 1x1 {l1} differ by {diff:.4g} > "
           f"{MESH_LOSS_ATOL}")
    _check(gdiff <= MESH_GRAD_NORM_RTOL,
           f"mesh: 2x2 grad norms {gw} vs 1x1 {g1} differ by {gdiff:.4g} "
           f"(relative) > {MESH_GRAD_NORM_RTOL}")
    return {
        "model": cfg.name, "layers": cfg.num_layers,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "steps": steps, "losses_2x2": lw, "losses_1x1": l1,
        "max_abs_loss_diff": diff, "check_tol_abs": MESH_LOSS_ATOL,
        "grad_norms_2x2": gw, "grad_norms_1x1": g1,
        "max_rel_grad_norm_diff": gdiff,
        "check_tol_grad_norm_rel": MESH_GRAD_NORM_RTOL,
        "step_s_2x2": [row["seconds"] for row in wide],
        "step_s_1x1": [row["seconds"] for row in one],
    }


def one_period(cfg):
    """zamba2-2.7b at its published widths, cut in depth to one period of
    the layer pattern: six Mamba2 layers and one application of the shared
    attention block (the full model repeats this nine times)."""
    return dataclasses.replace(cfg, num_layers=cfg.attn_every)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-vs-1x1 Trainer comparison")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    cache = compile_cache_dir(os.environ)
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", cache)

    full = get_config(MODEL)
    if args.chips == 4:
        phases = [("mesh", lambda: mesh_phase(
            one_period(full), ShapeConfig("smoke_mesh", "train", 2048, 2)))]
    else:
        phases = [
            ("serve", lambda: serve_phase(full)),
            ("train", lambda: train_phase(
                one_period(full), ShapeConfig("smoke_train", "train", 4096, 1),
                make_mesh((1, 1), ("data", "model")))),
        ]

    clock = CompileClock()
    for name, run in phases:
        snap, t0 = clock.snapshot(), time.perf_counter()
        obs = run()
        # the Engine's jitted closures hold it in a reference cycle: free
        # its weights before the next phase allocates
        gc.collect()
        obs.update(clock.since(snap))
        obs["phase_wall_s"] = time.perf_counter() - t0
        # the device allocator's peak since this process started
        obs["peak_bytes_in_use"] = (devs[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        obs["device_kind"] = devs[0].device_kind
        print(json.dumps({"phase": name, "smoke_observations": obs}),
              flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
