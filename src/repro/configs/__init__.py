"""Architecture registry: one module per assigned arch, exact public configs.

Each module exposes FULL (the assigned configuration) and SMOKE (a reduced
same-family configuration for CPU tests). ``get(name)`` returns the module;
``ARCHS`` lists all ids; ``SHAPES`` the assigned input-shape families.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

ARCHS = [
    "rwkv6_1p6b",
    "mixtral_8x22b",
    "llama4_maverick",
    "hymba_1p5b",
    "qwen2_7b",
    "gemma2_27b",
    "command_r_35b",
    "minicpm3_4b",
    "whisper_medium",
    "paligemma_3b",
    "deepseek_v2_lite",
]

_ALIASES = {
    "rwkv6-1.6b": "rwkv6_1p6b",
    "mixtral-8x22b": "mixtral_8x22b",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "hymba-1.5b": "hymba_1p5b",
    "qwen2-7b": "qwen2_7b",
    "gemma2-27b": "gemma2_27b",
    "command-r-35b": "command_r_35b",
    "minicpm3-4b": "minicpm3_4b",
    "whisper-medium": "whisper_medium",
    "paligemma-3b": "paligemma_3b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get(name: str):
    name = _ALIASES.get(name, name).replace("-", "_").replace(".", "p")
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {ARCHS}")
    return importlib.import_module(f"repro.configs.{name}")


def cells(include_skipped: bool = False):
    """All (arch × shape) assignment cells; marks the documented skips."""
    out = []
    for a in ARCHS:
        mod = get(a)
        for s in SHAPES.values():
            skip = skip_reason(mod.FULL, s)
            if skip and not include_skipped:
                out.append((a, s.name, skip))
            else:
                out.append((a, s.name, skip))
    return out


def skip_reason(cfg, shape: ShapeSpec) -> Optional[str]:
    """The assignment's documented skips (see DESIGN.md §Arch table)."""
    if shape.name == "long_500k":
        if cfg.rwkv or cfg.hybrid:
            return None
        if cfg.window or cfg.local_global_period:
            # SWA / local-global archs still need the full cache for their
            # global layers at 500k — run them (window bounds compute).
            return None
        return ("pure full-attention arch: 500k decode needs sub-quadratic "
                "attention — skipped per assignment")
    if cfg.enc_dec and shape.kind in ("decode", "prefill"):
        return None  # runs at the decoder's architectural max (448), noted
    return None
