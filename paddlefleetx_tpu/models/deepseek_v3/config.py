"""Hyper-parameters of a DeepSeek-V3-style decoder (``model_type:
deepseek_v3``), under the architecture's own (Hugging Face) key names.

Two keys say what THIS process holds of a deployment that shares each
layer between chips: ``experts_held`` (a half-open range of the
``n_routed_experts`` the router scores) and ``vocab_held`` (a half-open
range of the vocabulary's rows; token ids, logits and the loss are over
that slice). Both default to everything.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config:
    """Frozen hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144          # the leading dense MLPs
    moe_intermediate_size: int = 768       # one routed expert
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None      # only None is implemented
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1000000.0
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    #: [lo, hi) of the routed experts whose weights live here
    experts_held: Optional[Tuple[int, int]] = None
    #: [lo, hi) of the vocabulary rows whose embedding and head live here
    vocab_held: Optional[Tuple[int, int]] = None
    # framework knobs, as GPTConfig has them
    use_recompute: bool = False
    recompute_granularity: str = "full"
    scan_layers: bool = True
    use_flash_attention: bool = True
    loss_chunks: int = 1
    dtype: str = "float32"
    param_dtype: str = "float32"

    def __post_init__(self):
        for name in ("experts_held", "vocab_held"):
            value = getattr(self, name)
            if value is not None:
                object.__setattr__(self, name, tuple(int(v) for v in value))
        if self.q_lora_rank is not None:
            raise ValueError("q_lora_rank: only null (a full-rank query "
                             "projection) is implemented")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "the router implemented is scoring_func 'sigmoid' with "
                f"topk_method 'noaux_tc', not {self.scoring_func!r} / "
                f"{self.topk_method!r}")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing (n_group > 1) is not "
                             "implemented")
        if not self.norm_topk_prob:
            raise ValueError("norm_topk_prob false is not implemented")
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no "
                             f"range of {self.n_routed_experts} experts")
        lo, hi = self.held_vocab
        if not 0 <= lo < hi <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab_held} is no range "
                             f"of {self.vocab_size} rows")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the depth")

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def held_vocab(self) -> Tuple[int, int]:
        return self.vocab_held or (0, self.vocab_size)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @classmethod
    def from_config(cls, config) -> "DeepSeekV3Config":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        fields = {f.name for f in dataclasses.fields(cls)}
        # a null key (q_lora_rank, the *_held ranges) is its default
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
