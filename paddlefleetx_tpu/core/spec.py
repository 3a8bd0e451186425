"""Draft sources for speculative decoding on the slot server.

A draft source proposes, per request, ``k`` guesses for the tokens the
request will emit AFTER the one the current tick samples (``verify_step``
scores the window ``[t0, d_1..d_k]`` in one forward — see
``models/gpt/generation.py``). Drafts only affect throughput, never
output: a wrong draft just wastes its window column.

Two kinds of source stand behind ``GenerationConfig.spec_method``
(:func:`make_draft_source`):

* a HOST source (:class:`DraftSource`, ``"ngram"``): an object the
  server asks before every launch, with the request's committed
  history. The shipped one is n-gram self-speculation ("prompt
  lookup"): match the request's trailing n-gram against its own
  earlier history and propose the continuation that followed last
  time. It needs no second model and pays off on the repetitive spans
  (code, lists, quoted context) where speculative decoding wins most.
  It reads the newest committed tokens, so its server reads every
  launch in the step that made it.
* a source ON THE DEVICE (:class:`ModelDraftSource`, ``"mtp"``): the
  model's own multi-token-prediction block drafts inside the tick
  program (``models/exaone_moe``), from hidden states and a cache of
  its own that never leave the chip. The server asks the MODEL, not
  the host: it hands the tick no drafts, and since such a source needs
  nothing from the tick in flight, its launches are read a step late
  like a plain server's (``core/serving.py``, "Deferred harvest").
"""

from __future__ import annotations

from typing import Protocol, Sequence


class DraftSource(Protocol):
    """Per-request draft proposal interface."""

    def propose(self, history: Sequence[int], k: int) -> list[int]:
        """Return exactly ``k`` guesses for the tokens following
        ``history`` PLUS the one token the verify tick samples itself
        (i.e. guesses for positions ``len(history) + 2 ..``, given that
        position ``len(history) + 1`` is sampled, not drafted).

        ``k`` is not always ``gen_cfg.spec_tokens``: the fused
        multi-tick server (``device_loop_ticks=T`` — docs/inference.md,
        "Device-resident decode") proposes ``spec_tokens * T`` in ONE
        call and verifies chunk ``j`` on device tick ``j``, so later
        chunks guess past tokens the source never saw committed. A
        source only needs to return ``k`` in-vocab ids; staleness
        costs accept rate, never correctness."""
        ...


class NgramDraftSource:
    """Suffix-match the last ``n`` tokens of ``history`` (``n`` from
    ``max_ngram`` down to 1) against earlier history; on a hit at
    position ``i`` the continuation ``history[i + n] ..`` is what
    followed that n-gram last time. Its first token ``g0`` is a guess
    for the tick's own sampled ``t0``, so the k DRAFTS are the
    continuation shifted by one. No match ⇒ zeros (cheap guaranteed
    rejection)."""

    def __init__(self, max_ngram: int = 3):
        if max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")
        self.max_ngram = max_ngram

    def propose(self, history: Sequence[int], k: int) -> list[int]:
        """Draft up to ``k`` tokens by replaying the continuation of
        the most recent n-gram match in ``history`` (longest n
        first); empty when nothing matches."""
        hist = list(history)
        L = len(hist)
        for n in range(min(self.max_ngram, L - 1), 0, -1):
            pattern = hist[L - n:]
            # most recent earlier occurrence whose continuation is
            # in-bounds; range end L-n-1 keeps the match strictly
            # before the suffix itself
            for i in range(L - n - 1, -1, -1):
                if hist[i:i + n] == pattern:
                    cont = hist[i + n:i + n + k + 1]
                    drafts = cont[1:k + 1]
                    return drafts + [0] * (k - len(drafts))
        return [0] * k


class ModelDraftSource:
    """The model's own multi-token-prediction block as draft source:
    nothing to ask on the host. ``tokens`` is how many drafts a tick
    the model's blocks give. A model without such a block refuses."""

    #: the tick program drafts; the host fills no draft array
    on_device = True

    def __init__(self, model):
        self.tokens = int(getattr(
            model.config, "num_nextn_predict_layers", 0))
        if not self.tokens:
            raise ValueError(
                f"spec_method='mtp' needs a model with a multi-token-"
                f"prediction block; {type(model).__name__} has none "
                f"(num_nextn_predict_layers)")


def make_draft_source(method: str, model=None, **kwargs):
    """Factory behind ``GenerationConfig.spec_method``: a host
    :class:`DraftSource`, or for ``"mtp"`` the ``model``'s own
    :class:`ModelDraftSource`."""
    if method == "ngram":
        return NgramDraftSource(**kwargs)
    if method == "mtp":
        return ModelDraftSource(model)
    raise ValueError(
        f"unknown spec_method {method!r} (supported: 'ngram', 'mtp')")
