"""Architecture registry: --arch <id> -> config, shapes, input specs.

The 11 architectures x 4 shapes = 44 cells.  `long_500k`
requires sub-quadratic attention: it runs for the SSM/hybrid/mostly-local
archs and is a documented skip for the pure-full-attention ones
(DESIGN.md §4; EXPERIMENTS.md §Dry-run lists each skip).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

from . import (deepseek_moe_16b, deepseek_v2_lite, gemma3_27b, granite_8b,
               llama4_maverick_400b_a17b, mamba2_130m, phi3_medium_14b,
               qwen2_vl_7b, recurrentgemma_2b, smollm_135m, whisper_base)

__all__ = ["ARCHS", "SHAPES", "get_config", "get_smoke", "input_specs",
           "cell_supported", "all_cells"]

_MODULES = {
    "recurrentgemma-2b": recurrentgemma_2b,
    "phi3-medium-14b": phi3_medium_14b,
    "smollm-135m": smollm_135m,
    "gemma3-27b": gemma3_27b,
    "granite-8b": granite_8b,
    "mamba2-130m": mamba2_130m,
    "llama4-maverick-400b-a17b": llama4_maverick_400b_a17b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "deepseek-v2-lite": deepseek_v2_lite,
    "whisper-base": whisper_base,
    "qwen2-vl-7b": qwen2_vl_7b,
}

ARCHS = tuple(_MODULES)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}

# long_500k needs sub-quadratic attention over the 500k context:
_LONG_OK = {"recurrentgemma-2b", "mamba2-130m", "gemma3-27b"}
LONG_SKIP_REASON = (
    "pure full-attention decode over a 524288-token KV cache; assignment "
    "directs skip for non-SSM/hybrid/local archs (DESIGN.md §4)"
)


def get_config(arch: str) -> ModelConfig:
    return _MODULES[arch].CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _MODULES[arch].SMOKE


def cell_supported(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in _LONG_OK:
        return False, LONG_SKIP_REASON
    return True, ""


def all_cells():
    for a in ARCHS:
        for s in SHAPES:
            yield a, s, *cell_supported(a, s)


def input_specs(arch: str, shape: str, cfg: ModelConfig | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train/prefill: the full token (or stub-embedding) batch;
    decode: the current token; the cache/state enters separately via
    serve.engine.abstract_state.
    """
    cfg = cfg or get_config(arch)
    sp = SHAPES[shape]
    b, s = sp.global_batch, sp.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    sd = jax.ShapeDtypeStruct

    if sp.kind == "decode":
        out = {"tokens": sd((b, 1), i32)}
        if cfg.rope_mode == "mrope":
            out["rope_positions"] = sd((3, b, 1), i32)
        return out

    if cfg.family == "encdec":
        return {
            "frames": sd((b, cfg.encoder_seq, cfg.d_model), bf16),
            "tokens": sd((b, s), i32),
            **({"labels": sd((b, s), i32)} if sp.kind == "train" else {}),
        }
    if cfg.embeds_input:  # vlm stub: precomputed patch/text embeddings
        out = {"embeds": sd((b, s, cfg.d_model), bf16)}
        if cfg.rope_mode == "mrope":
            out["rope_positions"] = sd((3, b, s), i32)
        if sp.kind == "train":
            out["labels"] = sd((b, s), i32)
        return out
    out = {"tokens": sd((b, s), i32)}
    if sp.kind == "train":
        out["labels"] = sd((b, s), i32)
    return out
