"""Closed loop of full batches through ``serve.Engine.run_batch``.

Parameters (the cell file's ``params``): ``batch`` requests a batch, all
with prompts of ``prompt_len`` tokens (the Engine left-pads a ragged batch
with no mask, so equal lengths are the only ones it serves right) and
``new_tokens`` to generate, ``cache_len``.  Every ``greedy_every``-th
request is greedy, the others sample at ``temperature`` from their
``top_k`` best tokens; the greedy ones move one slot along with each
batch, so that every slot serves both kinds.  Prompts are uniform token
ids drawn from (seed, batch index).

The window closes at the end of the first batch that finishes after
``--seconds``, so every batch counted is whole.  The check draws, for
each slot of the batch, ``check_per_slot`` greedy and as many sampled
requests from the window's batches by the seed, runs the reference over
each one's prompt and served tokens, and compares at every served
position how far the served token's reference logit lies below the
reference's best (``served_gap``, greedy requests) or its ``top_k``-th
best (``sampled_gap``, sampled requests).
"""

from __future__ import annotations

import numpy as np

from compare import allowed, choose, served_gaps
from harness import Check, annotate


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell["params"]
        self.batches = []
        self._ref = None

    def _requests(self, index: int, new_tokens: int):
        from repro.serve.engine import Request
        p, V = self.p, self.ctx.model["vocab_size"]
        rng = np.random.default_rng((self.ctx.seed, index + 1))
        prompts = rng.integers(0, V, (p["batch"], p["prompt_len"]))
        return [Request(uid=(index + 1) * p["batch"] + i, prompt=row.tolist(),
                        max_new_tokens=new_tokens,
                        temperature=(0.0 if (i - index) % p["greedy_every"]
                                     == 0 else p["temperature"]),
                        top_k=p["top_k"])
                for i, row in enumerate(prompts)]

    def setup(self):
        from repro.serve.engine import Engine, EngineConfig
        ctx, p = self.ctx, self.p
        self.engine = Engine(ctx.weights(), ctx.cfg, ecfg=EngineConfig(
            max_batch=p["batch"], cache_len=p["cache_len"]))
        annotate(self.engine, "_prefill", "bench.prefill")
        annotate(self.engine, "_decode", "bench.decode")
        # one decode step compiles what every later one runs
        self.engine.run_batch(self._requests(-1, 2), seed=ctx.seed_31)

    def window(self, seconds: float) -> dict:
        rec, p, eng = self.ctx.rec, self.p, self.engine
        tokens = 0
        rec.open_window()
        while True:
            i = len(self.batches)
            with rec.span("bench.next_batch"):
                reqs = self._requests(i, p["new_tokens"])
            with rec.span("bench.run_batch"):
                eng.run_batch(reqs, seed=(self.ctx.seed_31 + i) % 2**31)
            rec.append("run_batch_s", rec.spans[-1].t1 - rec.spans[-1].t0)
            rec.append("prefill_s", eng.prefill_s)
            rec.append("decode_s", sum(eng.decode_step_s))
            rec.append("batches", {"batch": len(reqs),
                                   "prompt_len": p["prompt_len"],
                                   "decode_steps": len(eng.decode_step_s)})
            tokens += sum(len(r.output) for r in reqs)
            self.batches.append(reqs)
            rec.tick()
            if rec.elapsed() >= seconds:
                break
        rec.close_window()
        return {"gen_tokens_per_s": tokens / rec.window_s}

    @property
    def attempted(self) -> int:
        return sum(len(b) for b in self.batches)

    @property
    def failed(self) -> int:
        return sum(len(r.output) != r.max_new_tokens
                   for b in self.batches for r in b)

    def free(self):
        del self.engine

    def _picked(self):
        """For each slot, ``check_per_slot`` greedy and as many sampled
        requests, their batches drawn from the seed (all are of the
        longest length)."""
        rng = np.random.default_rng(self.ctx.seed)
        out = []
        for slot in range(self.p["batch"]):
            for greedy in (True, False):
                rows = [b[slot] for b in self.batches
                        if (b[slot].temperature <= 0) == greedy]
                n = min(self.p["check_per_slot"], len(rows))
                out += [rows[j] for j in sorted(rng.choice(len(rows), n,
                                                           replace=False))]
        return out

    def reference_logits(self, picked, rounding: str):
        """Reference logits (new_tokens, V) at every served position of
        each picked request: prompt and served tokens, one forward."""
        plen, n = self.p["prompt_len"], self.p["new_tokens"]
        return self.ctx.reference_logits(
            [r.prompt + r.output[:-1] for r in picked],
            np.arange(plen - 1, plen + n - 1), rounding)

    def _checks(self, picked, served):
        if self._ref is None:
            self._ref = self.reference_logits(picked, "exact")
        V, lim = self.ctx.model["vocab_size"], self.ctx.cell["limits"]
        gap = {"served_gap": 0.0, "sampled_gap": 0.0}
        for ref, tokens, r in zip(self._ref, served, picked):
            k = "served_gap" if r.temperature <= 0 else "sampled_gap"
            gap[k] = max(gap[k], float(served_gaps(ref, tokens,
                                                   allowed(r, V)).max()))
        return [Check(k, v, lim[k]) for k, v in gap.items()]

    def check(self):
        picked = self._picked()
        return self._checks(picked, [r.output for r in picked])

    def control(self):
        """The same comparison for the tokens an fp8 reference in the
        program's place picks, as the Engine picks them (the best, or a
        draw from the top k), at the same positions of the same
        requests."""
        picked = self._picked()
        rng = np.random.default_rng((self.ctx.seed, 1))
        low = self.reference_logits(picked, "fp8")
        return self._checks(picked, [choose(lg, r.temperature, r.top_k, rng)
                                     for lg, r in zip(low, picked)])
