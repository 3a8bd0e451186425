"""GPT model hyper-parameter container.

Field names and defaults follow the reference's ``Model`` YAML section
(reference ``single_model.py:475-510`` constructor signature and
``models/language_model/utils.py:39-110`` derivations: ffn defaults to
4*hidden, recompute granularity defaults to "full").
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Frozen GPT hyper-parameters (the YAML ``Model`` section)."""

    vocab_size: int = 51200
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    ffn_hidden_size: Optional[int] = None
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 16
    initializer_range: float = 0.02
    use_recompute: bool = False
    # full | full_attn | core_attn | save_dots (TPU-only: keep matmul
    # outputs, recompute elementwise — see _remat_policy)
    recompute_granularity: str = "full"
    fused_linear: bool = False            # no-op on TPU: XLA fuses bias
    fuse_attn_qkv: bool = True
    #: Megatron-SP: activations between the mp linears flow
    #: sequence-sharded over mp. With mp > 1 on a live mesh the four mp
    #: linears then run as the decomposed bidirectional-ring kernels
    #: (ops/collective_matmul.py), whose hops overlap the per-shard
    #: matmul chunks; a site whose shapes the rings cannot divide takes
    #: the plain GSPMD lowering — the dispatch matrix is
    #: docs/tensor_parallel.md.
    sequence_parallel: bool = False
    virtual_pp_degree: int = 1
    #: pipeline schedule when pp_degree > 1. "1F1B" (reference default,
    #: bounded activation memory via the explicit fwd/bwd-interleaved
    #: schedule), "zb" (zero-bubble: dX stays on the 1F1B critical
    #: path, dW is deferred into a bounded per-stage queue and drained
    #: during former bubble ticks — grads identical to 1F1B; see
    #: docs/pipeline.md), "zb_h2" (zero-bubble H2: extra warm-up
    #: forwards spend HBM headroom to also fill the fill-phase bubble;
    #: depth from ``zb_h2_depth``, validated against the device budget
    #: by parallel/pp_memory.py), "zb_auto" (pick the deepest feasible
    #: 1F1B -> zb -> zb_h2@depth rung for the memory budget and log
    #: the decision), or "GPipe" (all-forwards-then-autodiff).
    #: Case-insensitive, '-' and '_' interchangeable; canonicalized in
    #: __post_init__.
    pipeline_schedule: str = "1F1B"
    #: zb_h2 warm-up depth d: stage k may run up to
    #: min(2(pp*vpp-k)-1, (pp*vpp-k)+d) forwards ahead of its backward
    #: wave (bubble (K-1-d)(K-d)/2, zero at d = K-1). -1 = deepest
    #: depth the HBM budget admits (full depth when no budget is
    #: known). Ignored by the other schedules.
    zb_h2_depth: int = -1
    # TPU-specific knobs (absent in reference):
    scan_layers: bool = True              # lax.scan over layers
    use_flash_attention: bool = False     # Pallas kernel on TPU
    context_parallel: bool = False        # sequence sharded over the cp
    #                                       mesh axis (long context)
    #: cp algorithm: "ring" (exact ring attention, O((s/cp)^2) memory,
    #: ops/ring_attention.py) or "ulysses" (all-to-all: seq gathers
    #: while heads shard over cp x mp for the attention itself — two
    #: sharding constraints, XLA emits the all-to-alls; supports
    #: attention dropout, needs heads % (cp*mp) == 0)
    context_parallel_algo: str = "ring"
    #: >1: compute the LM loss over this many sequence chunks inside a
    #: rematerialized scan — the [b, s, V] logits tensor (the largest
    #: single activation: bs8 x s1024 x 50304 is 1.6 GB fp32) never
    #: materializes beyond one chunk. Trades one extra head matmul
    #: per chunk in backward for O(s/chunks) logits memory.
    loss_chunks: int = 1
    #: Mixture-of-Experts (beyond-reference; the reference has no MoE,
    #: SURVEY §2.2 EP row). 0 = dense FFN. >0: every decoder block's
    #: FFN becomes ``moe_num_experts`` routed experts (models/gpt/moe.py),
    #: expert-parallel over ``Distributed.ep_degree`` dataflow devices.
    moe_num_experts: int = 0
    moe_top_k: int = 2                    # experts per token
    moe_capacity_factor: float = 1.25     # slots = ceil(k*s*cf/E)
    moe_aux_loss_weight: float = 0.01     # Switch load-balance loss
    moe_z_loss_weight: float = 0.0        # router z-loss (off by default)
    #: How routed tokens reach their experts (docs/moe.md):
    #: "einsum" — dense one-hot [b, s, E, C] dispatch/combine einsums
    #:   (the parity/fallback reference; O(b·s·E·C·h) pack/unpack);
    #: "sort" — counting-sort gather into the contiguous per-expert
    #:   [E, b, C, h] buffer and gate-weighted scatter-combine back
    #:   (O(b·s·k·h) data movement, identical dropped-token set);
    #: "sort_pallas" — "sort" dispatch + the Pallas grouped expert
    #:   GEMM (ops/pallas/grouped_matmul.py) that skips empty expert
    #:   groups from the routing counts (falls back to the XLA expert
    #:   einsums per-site when the kernel rejects the shape).
    moe_dispatch: str = "einsum"
    #: Paged KV serving (core/paging.py + core/serving.py): fixed page
    #: size in TOKENS. 0 = contiguous per-slot cache (the default; the
    #: training path never pages). When > 0 it must be a multiple of
    #: 128 — the same lane-width rounding ``cache_capacity`` applies —
    #: and divide ``cache_capacity``, so a slot's logical capacity is
    #: exactly ``max_kv_pages`` pages and every page tiles the
    #: flash-decode kernel.
    kv_page_size: int = 0
    #: Physical pages in the global KV pool (the per-layer cache leaf
    #: becomes ``[kv_pool_pages, heads, head_dim, kv_page_size]``).
    #: Page 0 is the reserved null page, so the pool must hold at
    #: least ``max_kv_pages + 1`` pages — one maximum-length request
    #: plus the null page — or a single request could deadlock the
    #: server. Required (> 0) whenever ``kv_page_size`` is set.
    kv_pool_pages: int = 0
    #: Decode KV-cache storage dtype (docs/quantization.md). "bf16"
    #: stores the cache in the compute dtype (the historical layout —
    #: the name covers fp32 compute too); "int8" stores K/V as int8
    #: plus one fp32 scale per (row, head, position), halving the
    #: cache bytes per token so the same pool HBM admits ~2x the
    #: paged slots. Both decode kernels (ragged and paged, verify
    #: windows included) dequantize in-kernel; the dense fallback
    #: widens up front (``attention/*_int8`` counters).
    kv_cache_dtype: str = "bf16"
    #: Dense-matmul execution (docs/quantization.md). "off" runs the
    #: fp kernels as ever; "weight_only_int8" expects the param tree
    #: a PTQ pass emitted (scripts/quantize_checkpoint.py: int8
    #: ``kernel`` + fp32 per-output-channel ``kernel_scale``) and
    #: routes qkv/out-proj/fc1/fc2 — the `_CollectiveDense` mp path
    #: included — through the weight-only int8 Pallas GEMM
    #: (ops/pallas/quantized_matmul.py; ``quant/*`` counters, per-site
    #: XLA dequantize-then-dot fallback).
    quant_execution: str = "off"
    #: Multi-tenant LoRA (docs/lora.md). 0 = off — the param tree is
    #: byte-identical to the base model (the ``_CollectiveDense``
    #: convention). > 0: every qkv/out-proj/fc1/fc2 site
    #: grows a stacked adapter pair ``lora_a [A, K, r]`` /
    #: ``lora_b [A, r, N]`` (A = ``lora_num_adapters`` resident bank
    #: rows) and the forward adds ``(alpha/r)·B[id](A[id](x))`` per
    #: batch row keyed by the traced ``adapter_ids`` argument —
    #: grouped Pallas GEMMs when the kernel admits the shape, XLA
    #: gather-einsum otherwise (``lora/{grouped,fallback}`` counters).
    lora_rank: int = 0
    #: Adapter bank rows (the stacked leading dim of every
    #: ``lora_a``/``lora_b``). Row 0 is the RESERVED zero adapter:
    #: adapter id 0 means "base model" and its delta is masked out
    #: structurally, so the parity pin never depends on bank contents.
    #: Must be >= 2 when ``lora_rank`` > 0 (at least one real adapter
    #: beside the reserved row).
    lora_num_adapters: int = 0
    #: LoRA scale numerator: the delta is ``(lora_alpha / lora_rank) *
    #: B(A(x))``. 0.0 (default) means alpha = rank, i.e. scale 1.0.
    lora_alpha: float = 0.0
    dtype: str = "float32"                # compute dtype (bf16 for AMP-O2)
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        if self.hidden_size % self.num_attention_heads != 0:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must "
                f"divide hidden_size ({self.hidden_size})")
        if self.recompute_granularity not in ("full", "full_attn",
                                              "core_attn", "save_dots"):
            raise ValueError(
                f"unknown recompute_granularity "
                f"{self.recompute_granularity!r}")
        canon = {"1f1b": "1F1B", "gpipe": "GPipe", "zb": "zb",
                 "zb_h2": "zb_h2", "zb_auto": "zb_auto"}.get(
            str(self.pipeline_schedule).lower().replace("-", "_"))
        if canon is None:
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r} "
                f"(expected '1F1B', 'zb', 'zb_h2', 'zb_auto' or "
                f"'GPipe')")
        object.__setattr__(self, "pipeline_schedule", canon)
        if self.zb_h2_depth < -1:
            raise ValueError(
                f"zb_h2_depth must be >= -1 (-1 = deepest feasible), "
                f"got {self.zb_h2_depth}")
        if self.context_parallel_algo not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown context_parallel_algo "
                f"{self.context_parallel_algo!r} (expected 'ring' or "
                f"'ulysses')")
        # No silent degradation (VERDICT r3 #5): neither the flash
        # kernel nor the ring-cp path implements attention-prob
        # dropout, so a TRAINING config combining them with
        # attention_probs_dropout_prob > 0 falls back to dense XLA
        # attention — materializing the [b, h, s, s] scores those
        # paths exist to avoid. Construction only WARNS (dropout is
        # inert under deterministic=True, so eval/generation use the
        # kernel regardless — a raise here would block legitimate
        # inference-only use of checkpoints whose config carries the
        # common 0.1 default); the TRAINING entry point refuses the
        # long-sequence OOM traps loudly (GPTModule._pp_setup).
        # Ulysses-cp gets no warning: its attention is dense per
        # head-shard BY DESIGN (O(s^2/cp) memory is its documented
        # trade against the ring), so dropout there is supported.
        if self.attention_probs_dropout_prob > 0.0 and not (
                self.context_parallel
                and self.context_parallel_algo == "ulysses"):
            if self.context_parallel and \
                    self.context_parallel_algo == "ring":
                from ...utils.log import logger
                logger.warning(
                    "context_parallel algo='ring' with "
                    "attention_probs_dropout_prob=%s: TRAINING would "
                    "fall back to dense attention, materializing the "
                    "full [b, h, s, s] scores ring attention exists "
                    "to avoid (the training module refuses this). "
                    "Set the prob to 0.0 or context_parallel_algo="
                    "'ulysses' (dense per head-shard by design; "
                    "supports dropout).",
                    self.attention_probs_dropout_prob)
            elif self.use_flash_attention:
                # with in-kernel dropout configured the kernel path
                # holds under training dropout — nothing to warn
                # about. The CONFIGURED check (env var + artifact
                # presence only) is deliberate: config construction
                # must not probe jax.devices() and initialize the
                # PJRT backend as a side effect; the device-kind
                # match happens at kernel-dispatch time
                from ...ops.attention import _kernel_dropout_configured
                if not _kernel_dropout_configured():
                    from ...utils.log import logger
                    logger.warning(
                        "use_flash_attention=True with "
                        "attention_probs_dropout_prob=%s: TRAINING "
                        "attention takes the dense XLA path — "
                        "in-kernel dropout is enabled by the "
                        "chip-certification artifact "
                        "(ops/pallas/dropout_cert.json, written by "
                        "scripts/validate_flash_dropout.py on a "
                        "passing live-chip run), which is absent or "
                        "overridden here; eval/generation still use "
                        "the kernel. Set the prob to 0.0 to train "
                        "through the flash kernel.%s",
                        self.attention_probs_dropout_prob,
                        " At max_position_embeddings >= 4096 the dense "
                        "[b, h, s, s] scores will not fit and the "
                        "training module refuses to start."
                        if self.max_position_embeddings >= 4096 else "")
        if self.moe_num_experts:
            if not 1 <= self.moe_top_k <= self.moe_num_experts:
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) must be in "
                    f"[1, moe_num_experts={self.moe_num_experts}]")
            if self.moe_capacity_factor <= 0:
                raise ValueError("moe_capacity_factor must be > 0")
            if self.moe_dispatch not in ("einsum", "sort",
                                         "sort_pallas"):
                raise ValueError(
                    f"unknown moe_dispatch {self.moe_dispatch!r} "
                    f"(expected 'einsum', 'sort' or 'sort_pallas')")
        # Paged-KV composition: the three sizes must agree BEFORE any
        # device allocation happens — a page that does not tile the
        # capacity (or the lane width) would knock decode off the
        # flash_decode_paged kernel or leave unreachable pool columns,
        # and an undersized pool deadlocks the first max-length request.
        if self.kv_page_size or self.kv_pool_pages:
            if self.kv_page_size <= 0:
                raise ValueError(
                    f"kv_pool_pages ({self.kv_pool_pages}) is set but "
                    f"kv_page_size is {self.kv_page_size}; paged KV "
                    f"needs both (set kv_page_size to a multiple of "
                    f"128 that divides cache_capacity "
                    f"{self.cache_capacity})")
            if self.kv_page_size % 128:
                raise ValueError(
                    f"kv_page_size ({self.kv_page_size}) must be a "
                    f"multiple of 128 — the same TPU-lane rounding "
                    f"cache_capacity uses, so every page tiles the "
                    f"flash-decode kernel's 128-aligned KV blocks")
            if self.cache_capacity % self.kv_page_size:
                raise ValueError(
                    f"cache_capacity ({self.cache_capacity}, "
                    f"max_position_embeddings "
                    f"{self.max_position_embeddings} rounded up to "
                    f"128) must be divisible by kv_page_size "
                    f"({self.kv_page_size}) so a slot's page table "
                    f"covers it exactly (max_kv_pages = "
                    f"capacity / page)")
            if self.kv_pool_pages < self.max_kv_pages + 1:
                raise ValueError(
                    f"kv_pool_pages ({self.kv_pool_pages}) must be at "
                    f"least max_kv_pages + 1 = {self.max_kv_pages + 1} "
                    f"(one maximum-length request's "
                    f"{self.max_kv_pages} pages plus the reserved "
                    f"null page 0), or a single request can deadlock "
                    f"the page pool")
        # Quantized execution knobs fail construction loudly: a typo'd
        # value silently running fp would defeat the whole A/B (the
        # YAML-side typo path is caught earlier by the config-warning
        # pass — utils/config.py)
        if self.kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r} "
                f"(expected 'bf16' or 'int8' — "
                f"docs/quantization.md)")
        if self.quant_execution not in ("off", "weight_only_int8"):
            raise ValueError(
                f"unknown quant_execution {self.quant_execution!r} "
                f"(expected 'off' or 'weight_only_int8' — "
                f"docs/quantization.md)")
        # LoRA knobs fail construction loudly for the same reason: a
        # typo'd rank silently serving the base model would defeat the
        # multi-tenant A/B entirely.
        if self.lora_rank < 0:
            raise ValueError(
                f"lora_rank must be >= 0, got {self.lora_rank}")
        if self.lora_alpha < 0:
            raise ValueError(
                f"lora_alpha must be >= 0, got {self.lora_alpha}")
        if self.lora_num_adapters and not self.lora_rank:
            raise ValueError(
                f"lora_num_adapters ({self.lora_num_adapters}) is set "
                f"but lora_rank is 0; multi-tenant LoRA needs both "
                f"(docs/lora.md)")
        if self.lora_rank:
            if self.lora_num_adapters < 2:
                raise ValueError(
                    f"lora_num_adapters ({self.lora_num_adapters}) "
                    f"must be >= 2 with lora_rank > 0 — row 0 is the "
                    f"reserved zero adapter (base model), so at least "
                    f"one real adapter row must exist (docs/lora.md)")
            if not self.fuse_attn_qkv:
                raise ValueError(
                    "lora_rank > 0 requires fuse_attn_qkv=True: the "
                    "adapter sites are exactly qkv/out-proj/fc1/fc2 "
                    "(docs/lora.md); the non-fused q/k/v projections "
                    "carry no adapter pair and would silently serve "
                    "partial adapters")
            if self.moe_num_experts:
                raise ValueError(
                    "lora_rank > 0 is incompatible with "
                    "moe_num_experts > 0: the MoE block replaces the "
                    "fc1/fc2 sites the adapter pair rides on "
                    "(docs/lora.md)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_kv_heads(self) -> int:
        """Heads of the KV cache's leaves: multi-head attention caches
        one K/V head a query head (``core/paging.py`` sizes pools by
        this name on every served config)."""
        return self.num_attention_heads

    @property
    def cache_capacity(self) -> int:
        """Decode KV-cache slots per row: ``max_position_embeddings``
        rounded UP to a multiple of 128 (the TPU lane width and the
        flash-decode block alignment), so the cache minor dim always
        tiles and an unaligned ``max_position_embeddings`` can never
        knock decode off the kernel path via the ``skv % block_kv``
        rejection in ``ops/pallas/flash_attention.py::flash_decode``.
        The extra slots are dead weight only: positions are still
        bounded by ``max_position_embeddings`` (the embedding table
        size) and causal/validity masking never reads them."""
        return -(-self.max_position_embeddings // 128) * 128

    @property
    def lora_scale(self) -> float:
        """Effective LoRA delta scale ``alpha / rank`` (1.0 when
        ``lora_alpha`` is 0.0 — the alpha = rank convention)."""
        if not self.lora_rank:
            return 0.0
        if not self.lora_alpha:
            return 1.0
        return self.lora_alpha / self.lora_rank

    @property
    def max_kv_pages(self) -> int:
        """Width of a slot's page table under paged KV serving:
        ``cache_capacity / kv_page_size`` logical pages cover one
        slot's full capacity. 0 when paging is off."""
        if not self.kv_page_size:
            return 0
        return self.cache_capacity // self.kv_page_size

    @classmethod
    def from_config(cls, config) -> "GPTConfig":
        """Build from a parsed YAML tree (Model + Engine sections)."""
        from ...utils.config import bf16_enabled
        model = dict(config.get("Model", {}))
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in model.items()
                  if k in fields and v is not None}
        if model.get("use_recompute") and \
                not model.get("recompute_granularity"):
            kwargs["recompute_granularity"] = "full"
        # AMP-O2 / use_pure_fp16 maps to bf16 compute on TPU
        if bf16_enabled(config):
            kwargs.setdefault("dtype", "bfloat16")
        return cls(**kwargs)
