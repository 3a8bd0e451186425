"""Hyper-parameters of a K-EXAONE-style decoder (``model_type:
exaone_moe``), under the architecture's own (Hugging Face) key names
(``rope_parameters.rope_theta`` flattened to ``rope_theta``), plus the
chip's share (``experts_held``) and what the slot server sets on its
twin config (``kv_page_size``, ``kv_pool_pages``,
``window_pool_pages``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

#: ``sliding_window_pattern`` "LLLG" of the published 48-layer model
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """Frozen hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    #: per layer, "sliding_attention" (rotary, a window) or
    #: "full_attention" (no position encoding, the whole context)
    layer_types: Tuple[str, ...] = PERIOD * 12
    #: per layer, the window in keys; 0 on a full layer
    sliding_windows: Tuple[int, ...] = (128, 128, 128, 0) * 12
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    rope_theta: float = 1000000.0
    #: per layer, "dense" (a gated MLP of ``intermediate_size``) or
    #: "sparse" (the routed experts and the shared one)
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    #: the routed experts this chip holds, a half-open range of the
    #: ``num_experts`` the router scores; None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: multi-token-prediction blocks behind the last layer (0 or 1)
    num_nextn_predict_layers: int = 1
    mtp_layer_types: Tuple[str, ...] = ("full_attention",)
    mtp_sliding_windows: Tuple[int, ...] = (0,)
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
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
    prefill_chunk: int = 512
    #: what the server asks of every served config
    kv_cache_dtype: str = "bf16"
    lora_rank: int = 0
    lora_num_adapters: int = 0

    def __post_init__(self):
        for name, cast in (("layer_types", str), ("mlp_layer_types", str),
                           ("sliding_windows", int),
                           ("mtp_layer_types", str),
                           ("mtp_sliding_windows", int)):
            object.__setattr__(
                self, name, tuple(cast(v) for v in getattr(self, name)))
        layers = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types", "sliding_windows"):
            if len(getattr(self, name)) < layers:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"{layers} layers")
        for kind, reach in zip(self.layer_types[:layers],
                               self.sliding_windows[:layers]):
            if kind not in ("sliding_attention", "full_attention") or \
                    reach != (self.sliding_window
                              if kind == "sliding_attention" else 0):
                raise ValueError(
                    f"a {kind!r} layer with a window of {reach}: sliding "
                    f"layers take sliding_window ({self.sliding_window}), "
                    f"full layers 0")
        if any(k not in ("dense", "sparse")
               for k in self.mlp_layer_types[:layers]):
            raise ValueError(f"mlp_layer_types {self.mlp_layer_types}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction block at most "
                             "is implemented")
        if self.num_nextn_predict_layers and (
                self.mtp_layer_types[:1] != ("full_attention",)
                or self.mtp_sliding_windows[:1] != (0,)):
            raise ValueError("the multi-token-prediction block "
                             "implemented is a full-attention layer")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(
                int(v) for v in self.experts_held))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no "
                             f"range of {self.num_experts} experts")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"into {self.num_key_value_heads} K/V heads")
        for name, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1), ("norm_topk_prob", True),
                           ("hidden_act", "silu"),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r} is not "
                                 f"implemented (the published {want!r} is)")
        if self.kv_cache_dtype != "bf16":
            raise ValueError("only a bf16 KV cache is implemented")
        if self.kv_page_size and self.cache_capacity % self.kv_page_size:
            raise ValueError(
                f"kv_page_size {self.kv_page_size} does not divide the "
                f"cache capacity {self.cache_capacity}")

    # the names the slot server, the pager and the modules this family
    # shares with others (models/smallthinker's Attention,
    # models/solar_open2's expert layer, models/granite_hybrid's gated
    # MLP) read off a config
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
    def sliding_window_size(self) -> int:
        return self.sliding_window

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    @property
    def shared_intermediate_size(self) -> int:
        """Width of the dense layers' gated MLP."""
        return self.intermediate_size

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def is_window(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def is_sparse(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "sparse"

    @property
    def window_layers(self) -> int:
        """Layers of the window class (the rest hold whole sequences)."""
        return sum(self.is_window(i)
                   for i in range(self.num_hidden_layers))

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values: the multi-token-prediction
        block is one more full layer of the page class."""
        return self.num_hidden_layers + self.num_nextn_predict_layers

    @property
    def verify_window(self) -> int:
        """Columns a verify tick writes: the sampled token and one
        draft a multi-token-prediction block."""
        return 1 + self.num_nextn_predict_layers

    @property
    def window_ring_pages(self) -> int:
        """Pages a slot holds on a window layer: the window, what one
        launch writes before the pages it pushes out are dead (a
        prefill chunk, or a verify tick's columns), and one for the
        window's unaligned first block; never more than a whole
        sequence. 0 without paging or without window layers."""
        if not self.kv_page_size or not self.window_layers:
            return 0
        ring = -(-(self.sliding_window
                   + max(self.prefill_chunk, self.verify_window))
                 // self.kv_page_size) + 1
        return min(ring, self.max_kv_pages)

    def window_class(self, num_slots: int, chunk: int
                     ) -> "ExaoneMoeConfig":
        """The twin config of a server of ``num_slots`` slots that
        prefills ``chunk`` tokens at a time: the window class's pool is
        one ring a slot behind the reserved null page."""
        cfg = dataclasses.replace(self, prefill_chunk=int(chunk))
        return dataclasses.replace(
            cfg, window_pool_pages=1 + num_slots * cfg.window_ring_pages)

    @classmethod
    def from_config(cls, config) -> "ExaoneMoeConfig":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        rope = model.pop("rope_parameters", None)
        if rope and rope.get("rope_theta") is not None:
            model.setdefault("rope_theta", float(rope["rope_theta"]))
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
