"""Work a Mamba2 stack with one shared attention block (zamba2, as the
registry builds it) needs, counted from its shapes.

The Mamba2 layers are counted as in ``work.ssm``.  The shared block is
counted once for each application (``num_layers / attn_every`` of them):
its projections and MLP as matmuls, causal attention over half of S² for
a full sequence and over the valid cache positions for a decode step.
Its KV cache is read over the valid positions only.
"""

from __future__ import annotations

from work import ssm
from work.ssm import BF16


def applications(m) -> int:
    return m["num_layers"] // m["attn_every"]


def attn_block_params(m) -> int:
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return 2 * d + d * q + 2 * d * kv + q * d + 3 * d * ff


def attn_block_flops_per_token(m) -> int:
    """Projections and MLP; the scores are counted apart."""
    return 2 * (attn_block_params(m) - 2 * m["d_model"])


def _scores_flops(m, batch: int, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs a sequence."""
    return 4 * m["n_heads"] * m["head_dim"] * pairs * batch


def forward_flops(m, batch: int, seq: int) -> float:
    per_token = (m["num_layers"] * ssm.mamba_flops_per_token(m)
                 + applications(m) * attn_block_flops_per_token(m)
                 + ssm.head_flops_per_token(m))
    return (batch * seq * per_token
            + applications(m) * _scores_flops(m, batch, seq * seq / 2))


def train_flops(m, batch: int, seq: int) -> float:
    return 3 * forward_flops(m, batch, seq)


def decode_flops(m, batch: int, pos: int) -> float:
    """One token for each of ``batch`` sequences with ``pos`` tokens in
    the cache already (the new token attends over pos + 1)."""
    return (forward_flops(m, batch, 1) - applications(m) *
            _scores_flops(m, batch, 0.5) +
            applications(m) * _scores_flops(m, batch, pos + 1))


def decode_bytes(m, batch: int, pos: int) -> int:
    kv_row = BF16 * 2 * m["n_kv_heads"] * m["head_dim"]
    return (ssm.weight_bytes(m, batch)
            + BF16 * attn_block_params(m)
            + 2 * m["num_layers"] * ssm.state_bytes(m, batch)
            + applications(m) * batch * (pos + 1) * kv_row
            + BF16 * batch * m["vocab_size"])
