"""SmallThinker-style decoder (grouped-query window / NoPE-global
attention + a top-k ReGLU expert layer routed before attention), on the
serving path."""

from .config import SmallThinkerConfig
from .model import SmallThinkerForCausalLM

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM"]
