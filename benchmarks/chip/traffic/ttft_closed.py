"""One client, one request at a time, through ``serve.Engine.run_batch``:
time to first token of long prompts.

Parameters (the cell file's ``params``): each request is one greedy
prompt of ``prompt_len`` tokens with one new token, against a cache of
``cache_len``.  The prompts are a pool of ``pool`` drawn from the seed,
sent in turn; the client sends the next request when the last returns.
A request's time runs from the harness calling ``run_batch`` to its
return with the token.  The check runs the reference over every distinct
prompt the window served and compares, for each, how far the served
token's reference logit lies below the reference's best.
"""

from __future__ import annotations

import numpy as np

from compare import choose, served_gaps
from harness import Check, annotate, percentile


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell["params"]
        self.served = []           # (pool index, token)
        self._ref = None

    def _request(self, uid: int, prompt):
        from repro.serve.engine import Request
        return Request(uid=uid, prompt=prompt.tolist(), max_new_tokens=1)

    def setup(self):
        from repro.serve.engine import Engine, EngineConfig
        ctx, p = self.ctx, self.p
        rng = np.random.default_rng(ctx.seed)
        V = ctx.model["vocab_size"]
        self.pool = rng.integers(0, V, (p["pool"], p["prompt_len"]))
        self.engine = Engine(ctx.weights(), ctx.cfg, ecfg=EngineConfig(
            max_batch=1, cache_len=p["cache_len"]))
        annotate(self.engine, "_prefill", "bench.prefill")
        annotate(self.engine, "_decode", "bench.decode")
        warm = rng.integers(0, V, p["prompt_len"])
        self.engine.run_batch([self._request(0, warm)], seed=ctx.seed_31)

    def window(self, seconds: float) -> dict:
        rec, eng = self.ctx.rec, self.engine
        rec.open_window()
        while True:
            i = len(self.served)
            k = i % len(self.pool)
            req = self._request(i + 1, self.pool[k])
            with rec.span("bench.run_batch"):
                eng.run_batch([req], seed=self.ctx.seed_31)
            rec.append("ttft_s", rec.spans[-1].t1 - rec.spans[-1].t0)
            rec.append("prefill_s", eng.prefill_s)
            rec.append("prefills", {"batch": 1,
                                    "prompt_len": self.p["prompt_len"]})
            self.served.append((k, req.output[0] if req.output else None))
            rec.tick()
            if rec.elapsed() >= seconds:
                break
        rec.close_window()
        return {"ttft_p90_s": percentile(rec.counters["ttft_s"], 90)}

    @property
    def attempted(self) -> int:
        return len(self.served)

    @property
    def failed(self) -> int:
        return sum(tok is None for _, tok in self.served)

    def free(self):
        del self.engine

    def _picked(self):
        """(pool index, token) of each distinct prompt served, its first
        serving: a prompt sent again is the same greedy request."""
        first = {}
        for k, tok in self.served:
            if tok is not None:
                first.setdefault(k, tok)
        return sorted(first.items())

    def reference_logits(self, picked, rounding: str):
        plen = self.p["prompt_len"]
        return self.ctx.reference_logits(
            [self.pool[k].tolist() for k, _ in picked], np.arange(plen - 1,
                                                                  plen),
            rounding)

    def _served_gap(self, picked, served) -> Check:
        if self._ref is None:
            self._ref = self.reference_logits(picked, "exact")
        gap = max(float(served_gaps(ref, [tok]).max())
                  for ref, tok in zip(self._ref, served))
        return Check("served_gap", gap, self.ctx.cell["limits"]["served_gap"])

    def check(self):
        picked = self._picked()
        return [self._served_gap(picked, [tok for _, tok in picked])]

    def control(self):
        """The same comparison for the token an fp8 reference in the
        program's place puts first."""
        picked = self._picked()
        low = self.reference_logits(picked, "fp8")
        return [self._served_gap(picked, [int(choose(lg, 0.0, 0, None)[0])
                                          for lg in low])]
