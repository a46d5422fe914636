"""Work counts and peaks of the chip benchmark: hand counts at a tiny
size, the counts at or below XLA's own FLOP count of the same program,
and the peaks table's refusal of an unknown chip."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
import harness
from work import hybrid, ssm

# d 64, expand 2 -> d_inner 128, head 16 -> 8 heads, state 16, conv 4
TINY_SSM = dict(num_layers=2, d_model=64, ssm_expand=2, ssm_headdim=16,
                ssm_state=16, ssm_groups=1, ssm_conv=4, vocab_size=256)
TINY_HYB = dict(TINY_SSM, num_layers=4, attn_every=2, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=128)


def test_mamba_counts_by_hand():
    # in_proj 64 x (2*128 + 2*16 + 8) = 64 x 296; conv over 128 + 32
    assert ssm.dims(TINY_SSM)["d_in_proj"] == 296
    assert ssm.mamba_flops_per_token(TINY_SSM) == (
        2 * 64 * 296 + 2 * 4 * 160 + 4 * 8 * 16 * 16 + 2 * 128 * 64)
    assert ssm.mamba_params(TINY_SSM) == (
        64 * 296 + 4 * 160 + 160 + 3 * 8 + 128 + 128 * 64 + 64)
    per_tok = 2 * 63744 + 2 * 64 * 256
    assert ssm.forward_flops(TINY_SSM, 3, 10) == 30 * per_tok
    assert ssm.train_flops(TINY_SSM, 3, 10) == 90 * per_tok


def test_ssm_decode_bytes_by_hand():
    B = 2
    weights = 2 * (2 * ssm.mamba_params(TINY_SSM) + 64 + 64 * 256 + B * 64)
    state = B * 2 * (8 * 16 * 16 + 3 * 160)
    assert ssm.decode_bytes(TINY_SSM, B, 99) == (
        weights + 2 * 2 * state + 2 * B * 256)


def test_hybrid_counts_by_hand():
    apps = 2
    block = 2 * 64 + 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert hybrid.attn_block_params(TINY_HYB) == block
    S = 10
    per_tok = (4 * 63744 + apps * 2 * (block - 128) + 2 * 64 * 256)
    scores = apps * 4 * 4 * 16 * (S * S / 2)
    assert hybrid.forward_flops(TINY_HYB, 1, S) == S * per_tok + scores
    # a decode step at 7 cached tokens attends over 8
    assert hybrid.decode_flops(TINY_HYB, 1, 7) == (
        per_tok + apps * 4 * 4 * 16 * 8)
    kv = apps * 1 * 8 * 2 * 2 * 2 * 16
    assert hybrid.decode_bytes(TINY_HYB, 1, 7) == (
        ssm.decode_bytes(TINY_HYB, 1, 7) + 2 * block + kv)


@pytest.mark.parametrize("arch,fam,S", [("mamba2-1.3b", ssm, 64),
                                         ("zamba2-2.7b", hybrid, 64)])
def test_flops_at_or_below_xla(arch, fam, S):
    """XLA's count of the same forward (layers unrolled so that it sees
    each) includes what the benchmark leaves out, never less."""
    from repro.configs.registry import get_config
    from repro.models import transformer as tfm
    from repro.models.config import ParallelConfig
    from repro.models.modules import split
    cfg = get_config(arch).reduced()
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    pcfg = ParallelConfig(scan_layers=False, remat="none")
    params = jax.eval_shape(lambda k: split(tfm.init(k, cfg))[0],
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, S), jnp.int32)
    fwd = jax.jit(lambda p, t: tfm.loss_fn(p, {"tokens": t, "labels": t},
                                           cfg, pcfg)[0])
    cost = fwd.lower(params, tok).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < fam.forward_flops(m, 2, S) <= cost["flops"]


def test_unknown_device_kind_raises():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
