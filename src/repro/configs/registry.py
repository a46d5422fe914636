"""Architecture registry — the assigned configs + the paper's workloads.

Each ``src/repro/configs/<id>.py`` defines ``CONFIG`` with the exact figures
from the assignment; this registry imports them and offers lookup by id for
``--arch <id>`` everywhere (launcher, dry-run, benchmarks, tests).

The (arch × shape) applicability matrix lives here too: ``cells()`` yields
every runnable cell and the reason string for every skipped one (recorded in
EXPERIMENTS.md §Dry-run per the task spec).
"""

from __future__ import annotations

import importlib
from typing import Dict, Iterator, List, Optional, Tuple

from repro.models.config import ModelConfig, ShapeConfig, SHAPES

ARCH_IDS = (
    "zamba2-2.7b",
    "llava-next-34b",
    "whisper-medium",
    "llama3.2-1b",
    "chatglm3-6b",
    "qwen3-32b",
    "qwen1.5-4b",
    "arctic-480b",
    "mixtral-8x7b",
    "mamba2-1.3b",
    "zamba2-7b",
)

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def shape_applicability(cfg: ModelConfig, shape: ShapeConfig
                        ) -> Tuple[bool, str]:
    """(runnable, reason-if-skipped)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, ("full quadratic attention; 512k-token decode requires "
                       "sub-quadratic attention (SSM/hybrid/SWA only) — skip "
                       "per task spec, noted in DESIGN.md")
    return True, ""


def cells(archs=ARCH_IDS, shapes=SHAPES) -> Iterator[Tuple[str, ModelConfig, ShapeConfig, bool, str]]:
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            ok, why = shape_applicability(cfg, s)
            yield a, cfg, s, ok, why
