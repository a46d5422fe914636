"""Work Zyphra's published Zamba2 needs (the ``zamba2`` family), counted
from its shapes as ``work.hybrid`` counts the registry's zamba2.

The Mamba2 layers are counted as in ``work.ssm``.  Each application of a
shared block (one for each of ``hybrid_layer_ids``) is counted once: its
q, k and v from the 2·d input, ``o`` back to d, the gated MLP, the MLP
adapter (d x r, then r x 2·d_ff) and the application's own d x d linear
into the Mamba2 layer, as matmuls; causal attention at ``head_dim`` over
half of S² for a full sequence and over the valid cache positions for a
decode step.  Norms, the concatenation and RoPE are not counted.
"""

from __future__ import annotations

from work import ssm
from work.ssm import BF16


def applications(m) -> int:
    return len(m["hybrid_layer_ids"])


def block_params(m) -> int:
    """One shared block: norms, attention from 2·d, the gated MLP."""
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return 3 * d + 2 * d * q + 2 * 2 * d * kv + q * d + 3 * d * ff


def application_params(m) -> int:
    """What each application adds: its adapter and its linear."""
    d, r = m["d_model"], m["adapter_rank"]
    return d * r + r * 2 * m["d_ff"] + d * d


def application_flops_per_token(m) -> int:
    """A block's matmuls with the application's own; scores apart."""
    return 2 * (block_params(m) - 3 * m["d_model"] + application_params(m))


def _scores_flops(m, batch: int, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs a sequence."""
    return 4 * m["n_heads"] * m["head_dim"] * pairs * batch


def forward_flops(m, batch: int, seq: int) -> float:
    per_token = (m["num_layers"] * ssm.mamba_flops_per_token(m)
                 + applications(m) * application_flops_per_token(m)
                 + ssm.head_flops_per_token(m))
    return (batch * seq * per_token
            + applications(m) * _scores_flops(m, batch, seq * seq / 2))


def train_flops(m, batch: int, seq: int) -> float:
    """Forward and backward (twice the forward); recomputation not counted."""
    return 3 * forward_flops(m, batch, seq)


def decode_flops(m, batch: int, pos: int) -> float:
    """One token for each of ``batch`` sequences with ``pos`` tokens in
    the cache already (the new token attends over pos + 1)."""
    return (forward_flops(m, batch, 1)
            + applications(m) * _scores_flops(m, batch, pos + 0.5))


def decode_bytes(m, batch: int, pos: int) -> int:
    """bf16 weights read once (every shared block, every application's
    own leaves), the SSM and conv states read and written, each
    application's KV over the valid positions, the logits."""
    kv_row = BF16 * 2 * m["n_kv_heads"] * m["head_dim"]
    return (ssm.weight_bytes(m, batch)
            + BF16 * m["n_shared_blocks"] * block_params(m)
            + BF16 * applications(m) * application_params(m)
            + 2 * m["num_layers"] * ssm.state_bytes(m, batch)
            + applications(m) * batch * (pos + 1) * kv_row
            + BF16 * batch * m["vocab_size"])
