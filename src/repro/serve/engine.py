"""Batched serving engine: continuous prefill + decode over a request queue.

A production-shaped loop on top of ``transformer.prefill``/``decode_step``:
requests are admitted up to the configured batch, prompts padded to a
common length and prefetched into the shared KV state, then decode steps
run for the whole batch with per-sequence stop handling and temperature /
top-k sampling.  Used by ``examples/serve_batch.py`` and the serving tests.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tfm
from repro.models.config import ModelConfig, ParallelConfig


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    stop_token: Optional[int] = None
    # filled by the engine
    output: Optional[List[int]] = None
    latency_s: float = 0.0


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    cache_len: int = 512
    # Serving SLO / traffic parameters (ISSUE 10): consumed by the
    # analytical serving cost model (core/serving via a serving
    # Objective) and recorded by `launch.dryrun --serving` next to the
    # measured per-token decode latency.  None = no SLO attached.
    target_p99_ms: Optional[float] = None
    arrival_rate_rps: Optional[float] = None


class Engine:
    def __init__(self, params, cfg: ModelConfig,
                 pcfg: Optional[ParallelConfig] = None,
                 ecfg: Optional[EngineConfig] = None):
        self.params = params
        self.cfg = cfg
        self.pcfg = (pcfg or ParallelConfig()).replace(remat="none")
        self.ecfg = ecfg or EngineConfig()
        # named functions, so that a profile shows the two programs as
        # jit_prefill and jit_decode_step
        def prefill(params, batch):
            return tfm.prefill(params, batch, cfg, self.pcfg,
                               self.ecfg.cache_len)

        def decode_step(params, tokens, state):
            return tfm.decode_step(params, tokens, state, cfg, self.pcfg)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step)
        # per-decode-step wall times of the most recent run_batch (first
        # entry includes the decode jit compile; dryrun --serving drops it)
        self.decode_step_s: List[float] = []
        # prefill wall time of the most recent run_batch (includes its
        # compile on the first call) and the (B, V) logits that chose that
        # batch's last tokens
        self.prefill_s: float = 0.0
        self.last_logits: Optional[jnp.ndarray] = None
        # host seconds of the most recent run_batch spent bringing logits
        # to the host and choosing tokens (spans engine.logits_to_host and
        # engine.sample)
        self.sample_s: float = 0.0

    def _sample(self, logits: jnp.ndarray, reqs: List[Request],
                key) -> np.ndarray:
        logits = np.asarray(logits, np.float32)
        out = np.zeros(len(reqs), np.int32)
        for i, r in enumerate(reqs):
            row = logits[i][:self.cfg.vocab_size]
            if r.temperature <= 0:
                out[i] = int(row.argmax())
                continue
            row = row / r.temperature
            if r.top_k:
                kth = np.partition(row, -r.top_k)[-r.top_k]
                row = np.where(row < kth, -np.inf, row)
            p = np.exp(row - row.max())
            p /= p.sum()
            out[i] = int(np.random.default_rng(
                (int(jax.random.key_data(key)[0]), r.uid)).choice(len(p), p=p))
        return out

    def _next_tokens(self, logits: jnp.ndarray, reqs: List[Request], key,
                     step: Optional[int] = None) -> Tuple[np.ndarray, Any]:
        """``_sample`` on the host, timed into ``sample_s``: the logits
        brought over, then (a decode ``step`` folded into ``key`` first)
        the tokens drawn.  Returns the tokens and the key drawn with."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.logits_to_host"):
            logits = np.asarray(logits, np.float32)
        with jax.profiler.TraceAnnotation("engine.sample"):
            if step is not None:
                key = jax.random.fold_in(key, step)
            out = self._sample(logits, reqs, key)
        self.sample_s += time.perf_counter() - t0
        return out, key

    def run_batch(self, requests: List[Request], seed: int = 0
                  ) -> List[Request]:
        """Serve one admission batch to completion.  A profile shows it as
        host spans: engine.run_batch around engine.prefill, then per step
        engine.logits_to_host, engine.sample and engine.decode."""
        if len(requests) > self.ecfg.max_batch:
            raise ValueError("admit at most max_batch requests")
        with jax.profiler.TraceAnnotation("engine.run_batch"):
            return self._run_batch(requests, seed)

    def _run_batch(self, requests: List[Request], seed: int
                   ) -> List[Request]:
        t0 = time.perf_counter()
        self.decode_step_s = []
        self.sample_s = 0.0
        key = jax.random.PRNGKey(seed)
        B = len(requests)
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(requests):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.prefill"):
            logits, state = self._prefill(self.params,
                                          {"tokens": jnp.asarray(toks)})
            logits.block_until_ready()
        self.prefill_s = time.perf_counter() - ts

        outs: List[List[int]] = [[] for _ in requests]
        done = np.zeros(B, bool)
        max_new = max(r.max_new_tokens for r in requests)
        next_tok, key = self._next_tokens(logits, requests, key)
        for step in range(max_new):
            for i, r in enumerate(requests):
                if not done[i]:
                    outs[i].append(int(next_tok[i]))
                    if (r.stop_token is not None and
                            next_tok[i] == r.stop_token) or \
                            len(outs[i]) >= r.max_new_tokens:
                        done[i] = True
            if done.all():
                break
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode"):
                logits, state = self._decode(
                    self.params, jnp.asarray(next_tok)[:, None], state)
                logits.block_until_ready()
            self.decode_step_s.append(time.perf_counter() - ts)
            next_tok, key = self._next_tokens(logits, requests, key,
                                              step)

        dt = time.perf_counter() - t0
        self.last_logits = logits
        for r, o in zip(requests, outs):
            r.output = o
            r.latency_s = dt
        return requests
