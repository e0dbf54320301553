"""Architecture registry: ``--arch <id>`` -> (ModelConfig, RunConfig).

Shape-cell applicability (skips recorded in the roofline table + DESIGN.md):
  * long_500k only for sub-quadratic archs (ssm / hybrid)
  * decode shapes skipped for encoder-only archs (audio)
"""

from __future__ import annotations

import dataclasses

from repro.configs import (gemma2_2b, hubert_xlarge, llama32_3b,
                           mamba2_370m, minitron_4b, nemotron4_15b, olmoe,
                           phi35_moe, qwen2_vl_72b, zamba2_1p2b,
                           zamba2_7b)
from repro.configs.base import SHAPES, ModelConfig, RunConfig, ShapeConfig

_MODULES = {
    "phi3.5-moe-42b-a6.6b": phi35_moe,
    "olmoe-1b-7b": olmoe,
    "mamba2-370m": mamba2_370m,
    "zamba2-1.2b": zamba2_1p2b,
    "zamba2-7b": zamba2_7b,
    "minitron-4b": minitron_4b,
    "llama3.2-3b": llama32_3b,
    "gemma2-2b": gemma2_2b,
    "nemotron-4-15b": nemotron4_15b,
    "hubert-xlarge": hubert_xlarge,
    "qwen2-vl-72b": qwen2_vl_72b,
}

ARCH_IDS = list(_MODULES)


def get_model_config(arch: str) -> ModelConfig:
    return _MODULES[arch].CONFIG


def get_run_config(arch: str, **overrides) -> RunConfig:
    base = dict(getattr(_MODULES[arch], "RUN_OVERRIDES", {}))
    base.update(overrides)
    return RunConfig(**base)


def cell_supported(arch: str, shape: str) -> tuple[bool, str]:
    """Is (arch x shape) a runnable cell?  Returns (ok, reason_if_not)."""
    cfg = get_model_config(arch)
    sh = SHAPES[shape]
    if cfg.family == "audio" and sh.kind == "decode":
        return False, "encoder-only arch: no autoregressive decode step"
    if shape == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, ("524k-ctx decode needs sub-quadratic attention; this "
                       "arch is full-attention (gemma2's global layers "
                       "included)")
    return True, ""


def all_cells() -> list[tuple[str, str, bool, str]]:
    """Every (arch, shape) cell with its support status."""
    out = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            ok, why = cell_supported(arch, shape)
            out.append((arch, shape, ok, why))
    return out
