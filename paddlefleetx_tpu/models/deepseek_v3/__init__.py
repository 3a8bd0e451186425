"""DeepSeek-V3-style decoder (MLA + dropless sigmoid-routed experts)."""

from .config import DeepSeekV3Config
from .model import DeepSeekV3ForPretraining
from .modules import DeepSeekV3Module

__all__ = ["DeepSeekV3Config", "DeepSeekV3ForPretraining",
           "DeepSeekV3Module"]
