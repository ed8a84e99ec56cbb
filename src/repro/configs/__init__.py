"""Architecture registry: the 10 assigned archs, the paper's bert-large,
and moonlight-16b-a3b (latent attention and expert-parallel MoE, trained
only: its mixer has no decode cache)."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

from repro.configs.base import SHAPES, Arch, InputShape, reduced_decoder

_MODULES = {
    "grok-1-314b": "grok_1_314b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "qwen2.5-14b": "qwen2_5_14b",
    "qwen2.5-32b": "qwen2_5_32b",
    "chameleon-34b": "chameleon_34b",
    "whisper-large-v3": "whisper_large_v3",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "mamba2-130m": "mamba2_130m",
    "gemma2-2b": "gemma2_2b",
    "bert-large": "bert_large",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ASSIGNED = [k for k in _MODULES
            if k not in ("bert-large", "moonlight-16b-a3b")]


def get_arch(name: str) -> Arch:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.ARCH


def all_archs() -> Dict[str, Arch]:
    return {name: get_arch(name) for name in _MODULES}


def reduced_arch(name: str) -> Arch:
    """Smoke-test variant: <=2 periods, d_model<=256, <=4 experts, vocab 1k."""
    arch = get_arch(name)
    if arch.kind == "decoder":
        return dataclasses.replace(arch, cfg=reduced_decoder(arch.cfg),
                                   zero3=False)
    if arch.kind == "encdec":
        small = dataclasses.replace(
            arch.cfg, n_layers=2, d_model=128, n_heads=4, d_ff=256,
            vocab=512, n_frames=16, max_target=64)
        return dataclasses.replace(arch, cfg=small, zero3=False)
    if arch.kind == "bert":
        small = dataclasses.replace(
            arch.cfg, n_layers=2, d_model=128, n_heads=4, d_ff=256,
            vocab=512, max_pos=128)
        return dataclasses.replace(arch, cfg=small, zero3=False)
    raise ValueError(arch.kind)


__all__ = ["SHAPES", "Arch", "InputShape", "ASSIGNED", "get_arch",
           "all_archs", "reduced_arch"]
