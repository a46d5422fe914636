"""Batched serving engine: continuous prefill + decode over a request queue.

A production-shaped loop on top of ``transformer.prefill``/``decode_step``:
requests are admitted up to the configured batch, prompts padded to a
common length and prefetched into the shared KV state, then decode steps
run for the whole batch with per-sequence stop handling.  Each step's
tokens are chosen on the device (greedy, or temperature / top-k sampling,
``choose_tokens``) and only the (B,) tokens come back to the host.  Used
by ``examples/serve_batch.py`` and the serving tests.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


def choose_tokens(logits, temperature, top_k, uid, key, step, *,
                  vocab: int, k: int):
    """Each row's next token from its logits (B, V_padded): the best of
    the first ``vocab`` (the first of equals, as ``np.argmax``) where the
    row's ``temperature`` is 0 or less; else a draw from the softmax at
    ``temperature`` over the logits at or above the row's ``top_k``-th
    best (ties at that value kept; the whole vocabulary where ``top_k``
    is 0).  ``k``, static, is at least the batch's largest ``top_k``.  Row
    i draws with ``fold_in(fold_in(key, step), uid[i])``, so the same key,
    step, uid and row give the same token in any slot of any batch."""
    z = logits[:, :vocab].astype(jnp.float32)
    greedy = jnp.argmax(z, axis=-1)
    z = z / jnp.where(temperature > 0, temperature, 1.0)[:, None]
    if k:
        best = lax.top_k(z, k)[0]                   # (B, k), descending
        kth = jnp.take_along_axis(best, jnp.clip(top_k - 1, 0, k - 1)[:, None],
                                  axis=-1)
        z = jnp.where((top_k[:, None] > 0) & (z < kth), -jnp.inf, z)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.fold_in(key, step), uid)
    drawn = jax.vmap(jax.random.categorical)(keys, z)
    return jnp.where(temperature > 0, drawn, greedy).astype(jnp.int32)


class _Rows(NamedTuple):
    """A batch's sampling rule as ``choose_tokens`` takes it, put on the
    device once a ``run_batch``."""
    temperature: jax.Array       # (B,) float32
    top_k: jax.Array             # (B,) int32
    uid: jax.Array               # (B,) uint32
    key: jax.Array               # the batch's key, from its seed
    k: int                       # the batch's largest top_k (static)


class Engine:
    def __init__(self, params, cfg: ModelConfig,
                 pcfg: Optional[ParallelConfig] = None,
                 ecfg: Optional[EngineConfig] = None):
        self.params = params
        self.cfg = cfg
        self.pcfg = (pcfg or ParallelConfig()).replace(remat="none")
        self.ecfg = ecfg or EngineConfig()
        # named functions, so that a profile shows the programs as
        # jit_prefill, jit_decode_step and jit__choose (the token choice)
        def prefill(params, batch):
            return tfm.prefill(params, batch, cfg, self.pcfg,
                               self.ecfg.cache_len)

        def decode_step(params, tokens, state):
            return tfm.decode_step(params, tokens, state, cfg, self.pcfg)

        def _choose(logits, temperature, top_k, uid, key, step, k):
            return choose_tokens(logits, temperature, top_k, uid, key, step,
                                 vocab=cfg.vocab_size, k=k)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step)
        self._choose = jax.jit(_choose, static_argnames="k")
        # per-decode-step wall times of the most recent run_batch (first
        # entry includes the decode jit compile; dryrun --serving drops it)
        self.decode_step_s: List[float] = []
        # prefill wall time of the most recent run_batch (includes its
        # compile on the first call) and the (B, V) logits that chose that
        # batch's last tokens
        self.prefill_s: float = 0.0
        self.last_logits: Optional[jnp.ndarray] = None
        # host seconds of the most recent run_batch spent choosing tokens
        # and bringing them to the host (spans engine.sample and
        # engine.logits_to_host)
        self.sample_s: float = 0.0

    def _rows(self, reqs: List[Request], seed: int) -> _Rows:
        """The batch's sampling rule on the device, its key from ``seed``."""
        top_k = np.array([max(r.top_k, 0) for r in reqs], np.int32)
        return _Rows(
            temperature=jnp.asarray(np.array([r.temperature for r in reqs],
                                             np.float32)),
            top_k=jnp.asarray(top_k),
            uid=jnp.asarray(np.array([r.uid & 0xFFFFFFFF for r in reqs],
                                     np.uint32)),
            key=jax.random.PRNGKey(seed),
            k=int(top_k.max()))

    def _sample(self, logits: jnp.ndarray, reqs: List[Request],
                key: Tuple[_Rows, int]) -> np.ndarray:
        """The tokens ``reqs`` are served from ``logits`` (B, V_padded),
        ``key`` the batch's rows paired with the step, timed into
        ``sample_s``: ``_choose`` dispatched (span engine.sample), then its
        (B,) tokens brought over (span engine.logits_to_host; the logits
        stay on the device)."""
        t0 = time.perf_counter()
        rows, step = key
        with jax.profiler.TraceAnnotation("engine.sample"):
            tokens = self._choose(logits, rows.temperature, rows.top_k,
                                  rows.uid, rows.key, step, k=rows.k)
        with jax.profiler.TraceAnnotation("engine.logits_to_host"):
            out = np.array(tokens)           # a copy the caller may write
        self.sample_s += time.perf_counter() - t0
        return out

    def run_batch(self, requests: List[Request], seed: int = 0
                  ) -> List[Request]:
        """Serve one admission batch to completion.  A profile shows it as
        host spans: engine.run_batch around engine.prefill, then per step
        engine.sample, engine.logits_to_host and engine.decode.  Step s's
        tokens (0 from the prefill) draw from the key of (``seed``, s)."""
        if len(requests) > self.ecfg.max_batch:
            raise ValueError("admit at most max_batch requests")
        with jax.profiler.TraceAnnotation("engine.run_batch"):
            return self._run_batch(requests, seed)

    def _run_batch(self, requests: List[Request], seed: int
                   ) -> List[Request]:
        t0 = time.perf_counter()
        self.decode_step_s = []
        self.sample_s = 0.0
        rows = self._rows(requests, seed)
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
        next_tok = self._sample(logits, requests, (rows, 0))
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
                    self.params, jnp.asarray(next_tok[:, None]), state)
                logits.block_until_ready()
            self.decode_step_s.append(time.perf_counter() - ts)
            next_tok = self._sample(logits, requests, (rows, step + 1))

        dt = time.perf_counter() - t0
        self.last_logits = logits
        for r, o in zip(requests, outs):
            r.output = o
            r.latency_s = dt
        return requests
