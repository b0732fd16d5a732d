"""Config registry: importing this package registers all assigned archs."""
from repro.configs.base import ArchConfig, get_arch, list_archs, register
from repro.configs.shapes import SHAPES, InputShape, get_shape
from repro.configs.paper_models import PAPER_MODELS, PaperModelConfig

# side-effect registration of the 10 assigned architectures
from repro.configs.llama4_maverick_400b_a17b import LLAMA4_MAVERICK
from repro.configs.mamba2_130m import MAMBA2_130M
from repro.configs.mixtral_8x22b import MIXTRAL_8X22B
from repro.configs.whisper_tiny import WHISPER_TINY
from repro.configs.tinyllama_1_1b import TINYLLAMA_1_1B
from repro.configs.glm4_9b import GLM4_9B
from repro.configs.zamba2_1_2b import ZAMBA2_1_2B
from repro.configs.minicpm_2b import MINICPM_2B
from repro.configs.paligemma_3b import PALIGEMMA_3B
from repro.configs.starcoder2_15b import STARCODER2_15B
# registered for the chip benchmark's hybrid LM cell; not among the
# assigned architectures below, whose full-size dry runs the tests make
from repro.configs.granite_4_0_h_micro import GRANITE_4_0_H_MICRO

ALL_ARCHS = (
    "llama4-maverick-400b-a17b", "mamba2-130m", "mixtral-8x22b",
    "whisper-tiny", "tinyllama-1.1b", "glm4-9b", "zamba2-1.2b",
    "minicpm-2b", "paligemma-3b", "starcoder2-15b",
)

__all__ = [
    "ArchConfig", "get_arch", "list_archs", "register", "SHAPES",
    "InputShape", "get_shape", "PAPER_MODELS", "PaperModelConfig",
    "ALL_ARCHS",
]
