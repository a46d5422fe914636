"""The plain float32 references against the program at reduced sizes, on
the benchmark's own weights: prefill, decode through the caches, the
loss; and the fp8 control visibly off."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
import weights
from reference import hybrid, ssm

INIT = {"embed_std": 0.02, "qk_gain": 2.0}


def _setup(arch):
    from repro.configs.registry import get_config
    from repro.models import transformer as tfm
    from repro.models.modules import split
    cfg = get_config(arch).reduced()
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    shapes = jax.eval_shape(lambda k: split(tfm.init(k, cfg))[0],
                            jax.random.PRNGKey(0))
    return cfg, m, weights.make(shapes, 11, INIT, m)


FAMILIES = [("mamba2-1.3b", ssm), ("zamba2-2.7b", hybrid)]


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_prefill_and_decode_match_reference(arch, ref):
    from repro.models import transformer as tfm
    from repro.models.config import ParallelConfig
    cfg, m, w = _setup(arch)
    pcfg = ParallelConfig(remat="none")
    S, V = 40, cfg.vocab_size
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S + 1), 0, V)
    want = ref.forward(w, toks, m, positions=np.arange(S - 1, S + 1))
    logits, state = tfm.prefill(w, {"tokens": toks[:, :S]}, cfg, pcfg, 64)
    np.testing.assert_allclose(logits[:, :V], want[:, 0], rtol=2e-4,
                               atol=2e-4)
    logits, _ = tfm.decode_step(w, toks[:, S:], state, cfg, pcfg)
    np.testing.assert_allclose(logits[:, :V], want[:, 1], rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_loss_and_grad_match_reference(arch, ref):
    from repro.models import transformer as tfm
    from repro.models.config import ParallelConfig
    cfg, m, w = _setup(arch)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    pl, pg = jax.value_and_grad(lambda p: tfm.loss_fn(
        p, batch, cfg, ParallelConfig())[0])(w)
    rl, rg = jax.value_and_grad(lambda p: ref.loss(p, batch, m))(w)
    assert abs(float(pl) - float(rl)) < 1e-5
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(rg)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


def test_fp8_control_is_off():
    cfg, m, w = _setup("zamba2-2.7b")
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0,
                              cfg.vocab_size)
    exact = hybrid.forward(w, toks, m)
    low = hybrid.forward(w, toks, m, ssm.fp8)
    rel = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert rel > 1e-2


def test_ssd_matches_the_recurrence():
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    b, S, H, P, G, N = 2, 37, 4, 8, 1, 8
    x = jax.random.normal(k[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (b, S, G, N))
    C = jax.random.normal(k[4], (b, S, G, N))
    h = jnp.zeros((b, H, P, N))
    ys = []
    for t in range(S):
        h = (h * jnp.exp(dt[:, t] * A)[..., None, None] +
             jnp.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B[:, t, 0]))
        ys.append(jnp.einsum("bn,bhpn->bhp", C[:, t, 0], h))
    np.testing.assert_allclose(ssm.ssd(x, dt, A, B, C, chunk=8),
                               jnp.stack(ys, 1), rtol=1e-4, atol=1e-4)
