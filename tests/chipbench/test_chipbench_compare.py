"""The comparisons that decide ``correct`` for a served model: the gap of
a served token below the reference's k-th best, and the server's own
choice (greedy, or a draw at a temperature from the top k) that the
control makes in the program's place."""

import numpy as np
import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
from compare import allowed, choose, served_gaps

REF = np.array([[5.0, 4.0, 3.0, 2.0, 1.0],
                [0.0, 1.0, 2.0, 3.0, 9.0]])


@pytest.mark.parametrize("tokens,top_k,want", [
    ([0, 4], 1, [0.0, 0.0]),          # the reference's best
    ([1, 3], 1, [1.0, 6.0]),          # below the best
    ([1, 3], 2, [0.0, 0.0]),          # within the top two
    ([3, 0], 2, [2.0, 3.0]),          # below the second best
    ([4, 0], 5, [0.0, 0.0]),          # every token allowed
])
def test_served_gaps_against_kth_best(tokens, top_k, want):
    assert served_gaps(REF, tokens, top_k).tolist() == want


def test_choose_keeps_to_top_k():
    rng = np.random.default_rng(3)
    logits = np.tile(np.arange(50, dtype=float) / 50, (400, 1))
    got = choose(logits, 0.7, 5, rng)
    assert set(got.tolist()) == {45, 46, 47, 48, 49}
    assert choose(REF, 0.0, 0, None).tolist() == [0, 4]
    # with no top-k every token can come
    assert len(set(choose(logits, 5.0, 0, rng).tolist())) > 40


def test_allowed():
    from repro.serve.engine import Request
    assert allowed(Request(0, [1], temperature=0.0, top_k=50), 99) == 1
    assert allowed(Request(0, [1], temperature=0.7, top_k=50), 99) == 50
    assert allowed(Request(0, [1], temperature=0.7, top_k=0), 99) == 99
