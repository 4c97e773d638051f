"""Architecture registry: ``--arch <id>`` resolves here."""
from repro.configs.base import (SHAPES, LayerSpec, MLAConfig, ModelConfig,
                                Segment, ShapeConfig, YaRNConfig, reduced,
                                supports, swa_variant)

from repro.configs import (chameleon_34b, deepseek_67b, deepseek_v2_lite,
                           deepseek_v3_671b, gemma3_27b, h2o_danube3_4b,
                           lstm_am_7khr, qwen2_5_3b, qwen3_moe_30b_a3b,
                           recurrentgemma_2b, whisper_medium, xlstm_350m)

ARCHS = {
    "recurrentgemma-2b": recurrentgemma_2b.CONFIG,
    "gemma3-27b": gemma3_27b.CONFIG,
    "deepseek-67b": deepseek_67b.CONFIG,
    "h2o-danube-3-4b": h2o_danube3_4b.CONFIG,
    "whisper-medium": whisper_medium.CONFIG,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b.CONFIG,
    "qwen2.5-3b": qwen2_5_3b.CONFIG,
    "chameleon-34b": chameleon_34b.CONFIG,
    "deepseek-v3-671b": deepseek_v3_671b.CONFIG,
    "deepseek-v2-lite": deepseek_v2_lite.CONFIG,
    "xlstm-350m": xlstm_350m.CONFIG,
    # the paper's own acoustic model
    "lstm-am-7khr": lstm_am_7khr.CONFIG,
    "lstm-am-teacher": lstm_am_7khr.TEACHER,
}

ASSIGNED = [k for k in ARCHS if not k.startswith("lstm-am")]


def get_arch(name: str) -> ModelConfig:
    if name.endswith("+swa"):
        return swa_variant(get_arch(name[: -len("+swa")]))
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]

__all__ = ["ARCHS", "ASSIGNED", "SHAPES", "get_arch", "get_shape", "supports",
           "reduced", "swa_variant", "ModelConfig", "ShapeConfig", "LayerSpec",
           "Segment", "MLAConfig", "YaRNConfig"]
