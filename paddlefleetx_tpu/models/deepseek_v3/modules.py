"""``DeepSeekV3Module``: the task module (``Model.module``) that puts
the DeepSeek-V3-style decoder through ``LanguageModule``, the chunked
loss and the Engine as they are."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import register_module
from ...core.module import LanguageModule
from ..language_utils import (
    chunked_nll_sums, masked_nll_sums, process_data_configs,
)
from .config import DeepSeekV3Config
from .model import DeepSeekV3ForPretraining, DeepSeekV3Model, head_logits

#: keys of the step's routing statistics, in the order the model sows
STEP_STATS = ("moe_held_picks", "moe_load_max_over_mean", "moe_picks")


@register_module("DeepSeekV3Module")
class DeepSeekV3Module(LanguageModule):
    """Causal-LM pretraining over the held vocabulary slice. Loss: mean
    token cross-entropy; no balance loss, and the router's selection
    bias is not updated (neither is given by the published config)."""

    def __init__(self, configs):
        process_data_configs(configs)
        super().__init__(configs)

    def get_model(self):
        self.model_config = DeepSeekV3Config.from_config(self.configs)
        return DeepSeekV3ForPretraining(self.model_config)

    def loss_and_stats(self, params, batch, rng, train: bool = True):
        """``(loss, stats)``: the Engine's optional contract for a
        module whose step has more to report than its loss; ``stats``
        maps ``STEP_STATS`` to float32 scalars."""
        del rng, train                       # no dropout anywhere
        tokens, _position_ids, labels, loss_mask = batch
        cfg = self.model_config
        lo = cfg.held_vocab[0]
        h, mods = DeepSeekV3Model(cfg).apply(
            {"params": params["model"]}, tokens, mutable=["stats"])
        head = params["lm_head"]
        if cfg.loss_chunks > 1:
            nll, count = chunked_nll_sums(
                h, lambda hh: head_logits(hh, head), labels - lo,
                loss_mask, cfg.loss_chunks)
        else:
            nll, count = masked_nll_sums(head_logits(h, head),
                                         labels - lo, loss_mask)
        sown = jax.tree.leaves(mods.get("stats", {}))
        moe = sown[0] if sown else jnp.zeros((3,), jnp.float32)
        return nll / jnp.maximum(count, 1.0), dict(zip(STEP_STATS, moe))

    @staticmethod
    def reduce_step_stats(stacked: Dict[str, jax.Array]):
        """Micro-batches' statistics into one step's: counts add, the
        load ratio takes its worst."""
        return {k: jnp.max(v) if k == "moe_load_max_over_mean"
                else jnp.sum(v) for k, v in stacked.items()}

    def loss_fn(self, params, batch, rng, train: bool = True):
        return self.loss_and_stats(params, batch, rng, train)[0]

    def input_spec(self):
        section = self._data_section()
        seq = section.dataset.max_seq_len if section \
            else self.model_config.max_position_embeddings
        micro = self.configs.Global.micro_batch_size
        return [((micro, seq), "int32")]

    def training_step_end(self, log_dict: Dict[str, Any]) -> None:
        log_dict.setdefault(
            "max_seq_len", self.configs.Data.Train.dataset.max_seq_len)
        super().training_step_end(log_dict)
