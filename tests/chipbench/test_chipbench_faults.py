"""The check fails where the timed path is broken.  Each test skips the
harness's look for a chip, drives the rest of a run at a tiny size with a
fault planted in the program underneath, and sees ``correct`` come out
false: a served token altered where the Engine produces it (a greedy
token, and one sampled row of a batch); a training step that returns its
state unchanged; half of the batch left out, the mean taken over the
rest.  (The one-chip cells have no exchange between chips to leave
out.)"""

import numpy as np
import pytest

from chipbench_util import TINY_MODEL


def _alter_tokens(monkeypatch, row=None):
    """Greedy tokens one id off; or, with ``row``, that row's sampled
    token replaced by its least likely one at every step."""
    from repro.serve.engine import Engine
    orig = Engine._sample
    V = TINY_MODEL["zamba2-2.7b"]["vocab_size"]

    def altered(self, logits, reqs, key):
        out = orig(self, logits, reqs, key)
        for i, r in enumerate(reqs):
            if row is None and r.temperature <= 0:
                out[i] = (out[i] + 1) % V
            elif i == row and r.temperature > 0:
                out[i] = int(np.asarray(logits[i], np.float32)[:V].argmin())
        return out
    monkeypatch.setattr(Engine, "_sample", altered)


@pytest.mark.parametrize("cell,row,number", [
    ("zamba2.gen", 2, "sampled_gap"),
    ("zamba2.gen", None, "served_gap"),
    ("zamba2.ttft-4k", None, "served_gap")])
def test_altered_token_fails(checkout, capsys, monkeypatch, cell, row,
                             number):
    _alter_tokens(monkeypatch, row)
    rc, line = checkout.run(cell, capsys=capsys)
    assert rc == 0 and line["correct"] is False
    gap = line["check"][number]
    assert gap["value"] > gap["limit"]


def test_unchanged_state_fails(checkout, capsys, monkeypatch):
    from repro.parallel import steps
    orig = steps.adam_update

    def unchanged(params, grads, state, ocfg):
        return params, state, orig(params, grads, state, ocfg)[2]
    monkeypatch.setattr(steps, "adam_update", unchanged)
    rc, line = checkout.run("mamba2.train-4k", capsys=capsys)
    assert rc == 0 and line["correct"] is False
    assert line["check"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_fails(checkout, capsys, monkeypatch):
    from repro.parallel import steps
    orig = steps.tfm.loss_fn

    def half(params, batch, *a, **k):
        return orig(params, {n: v[: v.shape[0] // 2]
                             for n, v in batch.items()}, *a, **k)
    monkeypatch.setattr(steps.tfm, "loss_fn", half)
    rc, line = checkout.run("mamba2.train-4k", capsys=capsys)
    assert rc == 0 and line["correct"] is False
    c = line["check"]
    assert any(c[k]["value"] > c[k]["limit"] for k in c)
