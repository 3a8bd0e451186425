"""Granite-4.0-H-style hybrid decoder (``model_type: granitemoehybrid``
with no experts: Mamba-2 state-space layers whose recurrent state lives
beside the page pool, a NoPE grouped-query layer one in ten, a dense
gated MLP in every layer, four multipliers and a tied head), on the
serving path."""

from .config import GraniteHybridConfig
from .model import GraniteHybridForCausalLM

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM"]
