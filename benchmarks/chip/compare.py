"""The comparisons that decide ``correct``: the program's outputs against
the plain float32 reference, as numbers set beside their limits."""

from __future__ import annotations

import statistics
from typing import Dict, Tuple

import numpy as np


def served_gaps(ref_logits, tokens, top_k: int = 1) -> np.ndarray:
    """For each position, how far the served token's reference logit lies
    below the reference's ``top_k``-th best: 0 where the reference could
    serve it too (its best, for a greedy request: ``top_k`` 1).
    ref_logits (n, V), tokens (n,)."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens).reshape(-1)
    kth = np.partition(ref, -top_k, axis=-1)[:, -top_k]
    return np.maximum(kth - ref[np.arange(len(tok)), tok], 0.0)


def choose(logits, temperature: float, top_k: int, rng) -> np.ndarray:
    """The tokens a server picks from ``logits`` (n, V): the best where
    ``temperature`` is 0, else a draw from the softmax at ``temperature``
    over the ``top_k`` best (all where ``top_k`` is 0)."""
    z = np.asarray(logits, np.float64)
    if temperature <= 0:
        return z.argmax(-1)
    z = z / temperature
    if top_k:
        kth = np.partition(z, -top_k, axis=-1)[:, -top_k, None]
        z = np.where(z < kth, -np.inf, z)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.array([rng.choice(len(row), p=row) for row in p])


def allowed(request, vocab: int) -> int:
    """How many of the reference's best tokens a request may be served:
    1 for a greedy request, its ``top_k`` (all where 0) for a sampled one."""
    if request.temperature <= 0:
        return 1
    return request.top_k or vocab


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep=None) -> Tuple[float, str]:
    """The worst leaf's |program norm - reference norm|, over the larger
    of that leaf's reference norm and the median leaf's.  ``keep`` names
    the leaves compared (all when None).  Returns (gap, leaf)."""
    names = [k for k in ref if keep is None or k in keep]
    median = statistics.median(ref[k] for k in ref)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], median) for k in names}
    worst = max(gaps, key=gaps.get)
    return float(gaps[worst]), worst


def moved_leaves(ref_grad: Dict[str, float], floor: float = 1e-3):
    """Leaves whose reference gradient is above ``floor`` x the median
    leaf's.  A leaf below it (a bias under a softmax, say) moves under
    Adam by round-off alone, so its change is no evidence either way."""
    median = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v > floor * median}
