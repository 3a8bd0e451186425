"""Hyper-parameters of a Solar-Open2-style decoder (``model_type:
solar_open2``), under the architecture's own (Hugging Face) key names
(``linear_attn_config`` flattened to ``linear_*``), plus the chip's
share (``experts_held``) and what the slot server sets on its twin
config (``kv_page_size``, ``kv_pool_pages``, ``state_rows``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Frozen hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    # the softmax layers (``gqa_layers``): grouped-query, no position
    # encoding (``use_rope: false``), a sigmoid gate on the output
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    use_gqa_gate: bool = True
    gqa_interval: int = 3
    #: layers that are softmax layers; every other layer is a Kimi
    #: delta (linear-attention) layer
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    # the linear-attention layers (``linear_attn_config``)
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    #: false: decay and output gate are low-rank (rank = the head size)
    kda_use_full_proj: bool = False
    #: true: the step size is ``2 sigmoid``, eigenvalues in (-1, 1)
    kda_allow_neg_eigval: bool = True
    # the expert layer, every layer (``first_k_dense_replace: 0``)
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: the routed experts this chip holds, a half-open range of the
    #: ``n_routed_experts`` the router scores; None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # framework knobs, as GPTConfig has them
    use_flash_attention: bool = True
    scan_layers: bool = False
    dtype: str = "float32"
    param_dtype: str = "float32"
    # paged serving: set by GenerationServer on its twin config
    kv_page_size: int = 0
    kv_pool_pages: int = 0
    #: rows of a linear-attention layer's state leaves: the null row
    #: and one a slot
    state_rows: int = 0
    #: what the server asks of every served config
    kv_cache_dtype: str = "bf16"
    lora_rank: int = 0
    lora_num_adapters: int = 0

    def __post_init__(self):
        object.__setattr__(self, "gqa_layers", tuple(
            int(v) for v in self.gqa_layers))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(
                int(v) for v in self.experts_held))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no "
                             f"range of {self.n_routed_experts} experts")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"into {self.num_key_value_heads} K/V heads")
        for name, want in (("use_rope", False), ("use_gqa_gate", True),
                           ("kda_use_full_proj", False),
                           ("kda_allow_neg_eigval", True),
                           ("norm_topk_prob", True),
                           ("tie_word_embeddings", False),
                           ("first_k_dense_replace", 0)):
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r} is not "
                                 f"implemented (the published {want!r} is)")
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

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def is_gqa(self, layer: int) -> bool:
        return layer in self.gqa_layers

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values in the page pool."""
        return sum(self.is_gqa(i) for i in range(self.num_hidden_layers))

    @property
    def state_layers(self) -> int:
        """Layers that hold a recurrent state a slot instead."""
        return self.num_hidden_layers - self.kv_layers

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: q, k and v side by side."""
        return 3 * self.linear_num_heads * self.linear_head_dim

    @property
    def state_row_bytes(self) -> int:
        """Bytes one slot holds on one linear-attention layer: the
        float32 state of every head and the convolution's tail in the
        activations' dtype."""
        import jax.numpy as jnp
        return self.linear_num_heads * self.linear_head_dim ** 2 * 4 + (
            self.short_conv_kernel_size - 1) * self.conv_channels \
            * jnp.dtype(self.dtype).itemsize

    def state_class(self, num_slots: int) -> "SolarOpen2Config":
        """The twin config of a server of ``num_slots`` slots: the
        state leaves hold one row a slot behind the null row."""
        return dataclasses.replace(self, state_rows=1 + num_slots)

    @classmethod
    def from_config(cls, config) -> "SolarOpen2Config":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
