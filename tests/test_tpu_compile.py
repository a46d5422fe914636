"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels,
zamba2-2.7b's decode and prefill programs, and the Engine's token choice.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests need no accelerator: they catch
block shapes, layouts and VMEM use that interpret mode accepts and the
chip's compiler refuses.  Widths are the real ones of the main path
(zamba2 / mamba2 heads, gradient-compression blocks).  Nothing runs and
no time is measured.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant8 import dequantize, quantize
from repro.kernels.reduce_tree import tree_reduce
from repro.kernels.ssd_scan import ssd_scan
from repro.models import transformer as tfm
from repro.models.config import ParallelConfig
from repro.models.modules import split
from repro.serve.engine import choose_tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hd", [80, 128])
def test_flash_attention_compiles(one_chip, hd):
    B, S, H = 1, 2048, 32
    qkv = ((B, S, H, hd), jnp.bfloat16)
    text = _compile_text(lambda q, k, v: flash_attention(q, k, v),
                         one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(one_chip):
    # mamba2-1.3b: 64 heads of 64, one B/C group of state 128
    B, S, H, hd, G, N = 1, 2048, 64, 64, 1, 128
    f32 = jnp.float32
    text = _compile_text(
        lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm), one_chip,
        ((B, S, H, hd), f32), ((B, S, H), f32), ((H,), f32),
        ((B, S, G, N), f32), ((B, S, G, N), f32))
    assert "tpu_custom_call" in text


def test_quantize_compiles(one_chip):
    text = _compile_text(lambda x: quantize(x, 1024), one_chip,
                         ((1 << 20,), jnp.float32))
    assert "tpu_custom_call" in text


def test_dequantize_compiles(one_chip):
    n, block = 1 << 20, 1024
    text = _compile_text(lambda q, s: dequantize(q, s, block), one_chip,
                         ((n,), jnp.int8), ((n // block,), jnp.float32))
    assert "tpu_custom_call" in text


def test_tree_reduce_compiles(one_chip):
    text = _compile_text(lambda x: tree_reduce(x), one_chip,
                         ((16, 1 << 20), jnp.bfloat16))
    assert "tpu_custom_call" in text


# zamba2-2.7b's stacked in_proj, a per-group slice of it, and its decode
# state: (layers, d_model, in_proj width), (batch 8) SSM state
STACKED = "bf16[54,2560,10448]"
GROUP_SLICE = re.compile(r"bf16\[(1,)?6,2560,10448\]")
STATE = "f32[54,8,80,64,64]"
RESULT = re.compile(r"= (\S+) ([\w-]+)\(")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_stack_compiles_without_stacked_copies(one_chip, program):
    """zamba2's 54 stacked Mamba2 layers run as one scan whose operands are
    the stacked weights and state whole: at real widths (decode: batch 8,
    cache 1024; prefill: 1 x 4096) no op makes a copy of the stacked
    in_proj or of the SSM state, nor a per-group slice, and the program
    needs under 2 GB of temporaries beside its arguments."""
    cfg = get_config("zamba2-2.7b")
    pcfg = ParallelConfig()
    sds = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = sds(jax.eval_shape(lambda: split(tfm.init(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))[0]))
    if program == "decode":
        state = sds(jax.eval_shape(lambda: tfm.init_decode_state(cfg, 8, 1024)))
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
        fn = lambda p, t, s: tfm.decode_step(p, t, s, cfg, pcfg)
        args = (params, tok, state)
    else:
        tok = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
        fn = lambda p, t: tfm.prefill(p, {"tokens": t}, cfg, pcfg, 4096)
        args = (params, tok)
    compiled = jax.jit(fn).lower(*args).compile()
    made = RESULT.findall(compiled.as_text())
    assert any(shape.startswith(STACKED) for shape, _ in made)
    for shape, op in made:
        assert not GROUP_SLICE.match(shape), (shape, op)
        if shape.startswith(STACKED):
            assert op in ("parameter", "get-tuple-element"), (shape, op)
        if shape.startswith(STATE):
            assert op != "copy", (shape, op)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("batch,k", [(8, 50), (1, 0)])
def test_choose_tokens_compiles(one_chip, batch, k):
    """The Engine's token choice at zamba2.gen's (batch 8, top-50) and
    zamba2.ttft-4k's (batch 1, greedy) shapes over the bf16 logits of a
    32000-token vocabulary: XLA's exact TopK where there is a top-k, no
    approximate one and no Pallas kernel, a few MB of temporaries."""
    compiled = jax.jit(lambda *a: choose_tokens(*a, vocab=32000, k=k)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((batch, 32000), jnp.bfloat16), ((batch,), jnp.float32),
            ((batch,), jnp.int32), ((batch,), jnp.uint32),
            ((2,), jnp.uint32), ((), jnp.int32))]).compile()
    text = compiled.as_text()
    assert ('custom_call_target="TopK"' in text) == bool(k)
    assert "Approx" not in text and "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
