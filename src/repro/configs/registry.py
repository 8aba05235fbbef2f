"""Architecture registry: --arch <id> -> (full config, reduced config, shapes).

Shape skips follow DESIGN.md §Arch-applicability:
  * long_500k only for sub-quadratic archs (ssm / hybrid);
  * all assigned archs have decoders, so decode shapes always run.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

from repro.configs.base import (LM_SHAPES, LONG_500K, ModelConfig, ShapeConfig)

_ARCH_MODULES = {
    "gemma2-9b": "repro.configs.gemma2_9b",
    "olmo-1b": "repro.configs.olmo_1b",
    "qwen2-7b": "repro.configs.qwen2_7b",
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    "whisper-small": "repro.configs.whisper_small",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2",
    "qwen3-moe-30b-a3b": "repro.configs.qwen3_moe",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "lfm2-8b-a1b": "repro.configs.lfm2_8b_a1b",
    "dlrm0": "repro.configs.dlrm0",
}

ASSIGNED_ARCHS = tuple(k for k in _ARCH_MODULES if k != "dlrm0")
ALL_ARCHS = tuple(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[name]).CONFIG


def get_reduced(name: str) -> ModelConfig:
    return importlib.import_module(_ARCH_MODULES[name]).reduced()


def shapes_for(name: str) -> Tuple[ShapeConfig, ...]:
    """The assigned shape cells for an arch, with documented skips applied."""
    cfg = get_config(name)
    if cfg.family == "dlrm":
        # DLRM has its own training shape (paper Fig 8: global batch scaled
        # with chips; 65536 at 256 chips).
        return (ShapeConfig("dlrm_train", "train", 1, 65536),)
    out: List[ShapeConfig] = []
    for s in LM_SHAPES:
        if s is LONG_500K and not cfg.supports_long_context():
            continue  # documented skip: full-attention arch at 500k context
        out.append(s)
    return tuple(out)


def all_cells() -> List[Tuple[str, ShapeConfig]]:
    """Every (arch, shape) dry-run cell, assigned archs only."""
    cells = []
    for arch in ASSIGNED_ARCHS:
        for s in shapes_for(arch):
            cells.append((arch, s))
    return cells


def skipped_cells() -> List[Tuple[str, str, str]]:
    """(arch, shape, reason) for every documented skip."""
    out = []
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        if not cfg.supports_long_context():
            out.append((arch, "long_500k",
                        "full-attention arch: 524288-token decode is quadratic"))
    return out
