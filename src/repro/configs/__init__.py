"""Config registry: the 10 assigned architectures (``--arch <id>``) plus
the paper's four edge models, and the four assigned input shapes."""
from typing import Dict

from repro.core.model_config import ModelSpec, ShapeSpec

from repro.configs import shapes as _shapes
from repro.configs.edge_models import EDGE_MODELS
from repro.configs.gemma3_4b import SPEC as GEMMA3_4B
from repro.configs.glm4_9b import SPEC as GLM4_9B
from repro.configs.granite_3_8b import SPEC as GRANITE_3_8B
from repro.configs.internvl2_2b import SPEC as INTERNVL2_2B
from repro.configs.lfm2_8b_a1b import SPEC as LFM2_8B_A1B
from repro.configs.llama4_scout_17b_a16e import SPEC as LLAMA4_SCOUT
from repro.configs.minitron_4b import SPEC as MINITRON_4B
from repro.configs.qwen2_moe_a2_7b import SPEC as QWEN2_MOE
from repro.configs.whisper_medium import SPEC as WHISPER_MEDIUM
from repro.configs.xlstm_350m import SPEC as XLSTM_350M
from repro.configs.zamba2_1_2b import SPEC as ZAMBA2_12B

ASSIGNED: Dict[str, ModelSpec] = {
    s.name: s for s in (
        QWEN2_MOE, LLAMA4_SCOUT, GLM4_9B, GRANITE_3_8B, MINITRON_4B,
        GEMMA3_4B, WHISPER_MEDIUM, INTERNVL2_2B, ZAMBA2_12B, XLSTM_350M,
    )
}

ARCHS: Dict[str, ModelSpec] = {**ASSIGNED, **EDGE_MODELS,
                               LFM2_8B_A1B.name: LFM2_8B_A1B}
SHAPES: Dict[str, ShapeSpec] = dict(_shapes.SHAPES)

# long_500k requires sub-quadratic attention (DESIGN.md §7 skip table).
LONG_CONTEXT_OK = ("zamba2-1.2b", "xlstm-350m", "gemma3-4b")


def get_arch(name: str) -> ModelSpec:
    if name not in ARCHS:
        raise KeyError(f"unknown arch '{name}'; have {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeSpec:
    return _shapes.get(name)


def cells(include_skipped: bool = False):
    """All assigned (arch x shape) dry-run cells, honoring the skip table."""
    for arch in ASSIGNED.values():
        for shape in SHAPES.values():
            skip = shape.name == "long_500k" and arch.name not in LONG_CONTEXT_OK
            if skip and not include_skipped:
                continue
            yield arch, shape, skip
