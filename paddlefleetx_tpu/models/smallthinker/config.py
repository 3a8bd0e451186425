"""Hyper-parameters of a SmallThinker-style decoder (``model_name:
smallthinker_*``), under the architecture's own (Hugging Face) key
names, plus what the slot server sets on its twin config
(``kv_page_size``, ``kv_pool_pages``, ``window_pool_pages``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

#: rope_layout / sliding_window_layout of the published 52-layer model:
#: layers 0, 4, 8, ... are global and carry no position encoding
PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Frozen hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
    #: per layer, 1 = rotate-half RoPE over the whole head, 0 = no
    #: position encoding; layers past the list repeat :data:`PERIOD`
    rope_layout: Tuple[int, ...] = PERIOD * 13
    #: per layer, 1 = keys behind ``sliding_window_size`` are masked
    sliding_window_layout: Tuple[int, ...] = PERIOD * 13
    sliding_window_size: int = 4096
    max_position_embeddings: int = 16384
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
    #: pages of the window class's pool: ``1 + slots * ring_pages``
    window_pool_pages: int = 0
    #: tokens of one prefill chunk (it sizes the ring)
    prefill_chunk: int = 256
    #: what the server asks of every served config
    kv_cache_dtype: str = "bf16"
    lora_rank: int = 0
    lora_num_adapters: int = 0

    def __post_init__(self):
        for name in ("rope_layout", "sliding_window_layout"):
            object.__setattr__(
                self, name, tuple(int(v) for v in getattr(self, name)))
            if len(getattr(self, name)) < self.num_hidden_layers:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"into {self.num_key_value_heads} K/V heads")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError("the router implemented is a softmax over "
                             "the picked logits")
        if self.tie_word_embeddings:
            raise ValueError("a tied head is not implemented")
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
    def window_layers(self) -> int:
        """Layers of the window class (the rest hold whole sequences)."""
        return sum(self.sliding_window_layout[:self.num_hidden_layers])

    @property
    def window_ring_pages(self) -> int:
        """Pages a slot holds on a window layer: the window, a prefill
        chunk written before the pages it pushes out are dead, and one
        for the window's unaligned first block; never more than a whole
        sequence (a ring that long never wraps). 0 without paging or
        without window layers."""
        if not self.kv_page_size or not self.window_layers:
            return 0
        ring = -(-(self.sliding_window_size + self.prefill_chunk)
                 // self.kv_page_size) + 1
        return min(ring, self.max_kv_pages)

    def window_class(self, num_slots: int, chunk: int
                     ) -> "SmallThinkerConfig":
        """The twin config of a server of ``num_slots`` slots that
        prefills ``chunk`` tokens at a time: the window class's pool is
        one ring a slot behind the reserved null page."""
        cfg = dataclasses.replace(self, prefill_chunk=int(chunk))
        return dataclasses.replace(
            cfg, window_pool_pages=1 + num_slots * cfg.window_ring_pages)

    @classmethod
    def from_config(cls, config) -> "SmallThinkerConfig":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
