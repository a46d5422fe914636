"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny size.

The script itself refuses to run without a TPU; these tests call its
phases directly, through the same Engine and Trainer entry points and
with the same checks and tolerances, on a reduced zamba2.
"""

import importlib.util
import math
from pathlib import Path

import pytest

from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models.config import ShapeConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    return get_config("zamba2-2.7b").reduced()


def test_serve_phase_tiny(smoke, tiny):
    obs = smoke.serve_phase(tiny, batch=2, prompt_len=24, new_tokens=4,
                            cache_len=64)
    assert obs["check_decode_vs_prefill_rel_l2"] <= smoke.SERVE_REL_L2_TOL
    assert obs["decode_step_p50_s"] > 0 and obs["prefill_s"] > 0


def test_train_phase_tiny(smoke, tiny):
    cfg = smoke.one_period(tiny)
    assert cfg.num_layers == tiny.attn_every
    obs = smoke.train_phase(cfg, ShapeConfig("tiny", "train", 64, 1),
                            make_mesh((1, 1), ("data", "model")), steps=2)
    assert len(obs["losses"]) == 2
    assert all(math.isfinite(x) for x in obs["losses"])


def test_checks_raise(smoke):
    with pytest.raises(smoke.SmokeCheckFailed):
        smoke._check(False, "boom")


def test_compile_cache_rule(smoke):
    assert smoke.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/c"}) is None
    assert smoke.compile_cache_dir({}) == str(ROOT / ".jax_cache")


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
