"""K-EXAONE-style decoder (post-norm blocks, QK-norm, window layers
with rotary positions and global layers without any, sigmoid-routed
experts with a shared one, a multi-token-prediction block that drafts
for the verify tick on the device), on the serving path."""

from .config import ExaoneMoeConfig
from .model import ExaoneMoeForCausalLM

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM"]
