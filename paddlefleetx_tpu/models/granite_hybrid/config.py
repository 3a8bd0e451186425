"""Hyper-parameters of a Granite-4.0-H-style decoder (``model_type:
granitemoehybrid``), under the architecture's own (Hugging Face) key
names, plus what the slot server sets on its twin config
(``kv_page_size``, ``kv_pool_pages``, ``state_rows``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

#: one period of the published 40 layers: a softmax layer at 5, 15, ...
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Frozen hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    #: per layer "mamba" (a state-space layer) or "attention"
    layer_types: Tuple[str, ...] = PERIOD * 4
    # the softmax layers: grouped-query, no position encoding, the
    # scores times ``attention_multiplier`` (NOT head_dim ** -0.5)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    attention_multiplier: float = 0.015625
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    # the state-space layers (Mamba-2)
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the feed-forward, every layer: the dense ``shared_mlp``
    shared_intermediate_size: int = 8192
    num_local_experts: int = 0
    # the stream's multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    # framework knobs, as GPTConfig has them
    use_flash_attention: bool = True
    scan_layers: bool = False
    dtype: str = "float32"
    param_dtype: str = "float32"
    # paged serving: set by GenerationServer on its twin config
    kv_page_size: int = 0
    kv_pool_pages: int = 0
    #: rows of a state-space layer's state leaves: the null row and one
    #: a slot
    state_rows: int = 0
    #: what the server asks of every served config
    kv_cache_dtype: str = "bf16"
    lora_rank: int = 0
    lora_num_adapters: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(
            str(v) for v in self.layer_types))
        if len(self.layer_types) < self.num_hidden_layers or any(
                kind not in ("mamba", "attention")
                for kind in self.layer_types):
            raise ValueError(
                f"layer_types {self.layer_types} does not give "
                f"{self.num_hidden_layers} layers a kind each")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"into {self.num_key_value_heads} K/V heads")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are "
                f"not {self.mamba_expand} x {self.hidden_size}")
        for name, want in (("position_embedding_type", "nope"),
                           ("attention_bias", False),
                           ("mamba_n_groups", 1),
                           ("mamba_conv_bias", True),
                           ("mamba_proj_bias", False),
                           ("num_local_experts", 0),
                           ("tie_word_embeddings", True)):
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r} is not "
                                 f"implemented (the published {want!r} is)")
        if not math.log2(self.query_scale).is_integer():
            raise ValueError(
                f"attention_multiplier {self.attention_multiplier} over "
                f"head_dim ** -0.5 is {self.query_scale}, no power of "
                f"two: scaling the queries by it would round them")
        if self.kv_cache_dtype != "bf16":
            raise ValueError("only a bf16 KV cache is implemented")
        if self.kv_page_size and self.cache_capacity % self.kv_page_size:
            raise ValueError(
                f"kv_page_size {self.kv_page_size} does not divide the "
                f"cache capacity {self.cache_capacity}")

    # the names the slot server and the pager read off every config
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def cache_capacity(self) -> int:
        return -(-self.max_position_embeddings // 128) * 128

    @property
    def max_kv_pages(self) -> int:
        if not self.kv_page_size:
            return 0
        return self.cache_capacity // self.kv_page_size

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == "attention"

    @property
    def query_scale(self) -> float:
        """What the queries are multiplied by so that the attention
        paths' own ``head_dim ** -0.5`` makes ``attention_multiplier``
        of it: 1/64 x 8 = 0.125 as published, a power of two (checked
        above), so the product is exact in every float dtype."""
        return self.attention_multiplier * math.sqrt(self.head_dim)

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values in the page pool."""
        return sum(self.is_attention(i)
                   for i in range(self.num_hidden_layers))

    @property
    def state_layers(self) -> int:
        """Layers that hold a recurrent state a slot instead."""
        return self.num_hidden_layers - self.kv_layers

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: x, B and C side by
        side."""
        return self.mamba_d_inner \
            + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_row_bytes(self) -> int:
        """Bytes one slot holds on one state-space layer: the float32
        state of every head and the convolution's tail in the
        activations' dtype."""
        import jax.numpy as jnp
        return self.mamba_d_inner * self.mamba_d_state * 4 + (
            self.mamba_d_conv - 1) * self.conv_channels \
            * jnp.dtype(self.dtype).itemsize

    def state_class(self, num_slots: int) -> "GraniteHybridConfig":
        """The twin config of a server of ``num_slots`` slots: the
        state leaves hold one row a slot behind the null row."""
        return dataclasses.replace(self, state_rows=1 + num_slots)

    @classmethod
    def from_config(cls, config) -> "GraniteHybridConfig":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
