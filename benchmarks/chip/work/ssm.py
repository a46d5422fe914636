"""Work a pure Mamba2 (SSD) stack needs, counted from its shapes.

``m`` is a configuration file's ``model`` mapping.  The counts are of what
the algorithm needs, so that no implementation can read above its peak:

* a matmul costs 2 x parameters used x tokens;
* the SSD layer is counted in its recurrent form, 4·H·P·N FLOPs a token
  (state update and read-out), whatever chunking the program uses;
* elementwise work (norms, gates, softplus) is not counted;
* the LM head is counted over ``vocab_size`` columns, not the padding;
* bytes are bf16 weights read once, bf16 states read and written, logits
  written: the least a decode step has to move.
"""

from __future__ import annotations

BF16 = 2


def dims(m):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    P, N = m["ssm_headdim"], m["ssm_state"]
    G, K = m.get("ssm_groups", 1), m.get("ssm_conv", 4)
    H = di // P
    conv_dim = di + 2 * G * N
    return dict(d=d, di=di, H=H, P=P, N=N, G=G, K=K, conv_dim=conv_dim,
                d_in_proj=2 * di + 2 * G * N + H)


def mamba_params(m) -> int:
    """One Mamba2 layer, its pre-norm included."""
    k = dims(m)
    return (k["d"] * k["d_in_proj"] + k["K"] * k["conv_dim"] + k["conv_dim"]
            + 3 * k["H"] + k["di"] + k["di"] * k["d"] + k["d"])


def mamba_flops_per_token(m) -> int:
    k = dims(m)
    return (2 * k["d"] * k["d_in_proj"] + 2 * k["K"] * k["conv_dim"]
            + 4 * k["H"] * k["P"] * k["N"] + 2 * k["di"] * k["d"])


def head_flops_per_token(m) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def state_bytes(m, batch: int) -> int:
    """One layer's decode state: the SSM state and the conv lag."""
    k = dims(m)
    return batch * BF16 * (k["H"] * k["P"] * k["N"]
                           + (k["K"] - 1) * k["conv_dim"])


def weight_bytes(m, batch: int) -> int:
    """Weights a decode step reads: every layer, the final norm and the
    head once, and only the batch's rows of the embedding."""
    d, V = m["d_model"], m["vocab_size"]
    return BF16 * (m["num_layers"] * mamba_params(m) + d + d * V + batch * d)


def forward_flops(m, batch: int, seq: int) -> int:
    return batch * seq * (m["num_layers"] * mamba_flops_per_token(m)
                          + head_flops_per_token(m))


def train_flops(m, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward); recomputation not counted."""
    return 3 * forward_flops(m, batch, seq)


def decode_flops(m, batch: int, pos: int) -> int:
    return forward_flops(m, batch, 1)


def decode_bytes(m, batch: int, pos: int) -> int:
    return (weight_bytes(m, batch)
            + 2 * m["num_layers"] * state_bytes(m, batch)
            + BF16 * batch * m["vocab_size"])
