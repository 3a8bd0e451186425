"""Solar-Open2-style hybrid decoder (Kimi-delta linear-attention layers
whose recurrent state lives beside the page pool, a gated NoPE
grouped-query layer one in four, a sigmoid-routed expert layer with a
shared expert in every layer), on the serving path."""

from .config import SolarOpen2Config
from .model import SolarOpen2ForCausalLM

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM"]
