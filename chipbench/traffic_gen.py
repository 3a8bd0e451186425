"""The one general generator of inputs: a training corpus and an
open-loop request schedule, both from the seed and the parameters of a
``chipbench/traffic/<mix>.json`` file.

Lengths and inter-arrival gaps are the stratified quantiles of their
distributions, so every run holds the same multiset of sizes. Their
ORDER is drawn from the mix's ``schedule_seed``: with a number there,
every run replays one fixed arrival pattern and ``--seed`` changes only
the token ids (and the weights); with ``null`` the order follows
``--seed``. At some hundred requests to a window the order alone moves a
queueing tail by tens of percent (PERF.md, PR 22), which is the seed
changing the work.
"""

import math
import os
from statistics import NormalDist

import numpy as np


def make_corpus(directory, vocab, n_docs, doc_len, seed, zipf=1.1,
                prefix="chipbench"):
    """``<prefix>_ids.npy`` + ``<prefix>_idx.npz``: a Zipf unigram
    stream (copied from ``chip_smoke.py::make_corpus``) — learnable at
    once, so the loss falls from ln V toward the unigram entropy."""
    os.makedirs(directory, exist_ok=True)
    rng = np.random.default_rng(seed)
    ranks = rng.permutation(vocab - 1)
    p = 1.0 / np.arange(1, vocab) ** zipf
    ids = ranks[rng.choice(vocab - 1, size=n_docs * doc_len,
                           p=p / p.sum())].astype(np.int32)
    lens = np.full(n_docs, doc_len, np.int32)
    ids[np.cumsum(lens) - 1] = vocab - 1          # document ends
    np.save(os.path.join(directory, prefix + "_ids.npy"), ids)
    np.savez(os.path.join(directory, prefix + "_idx.npz"), lens=lens)


def lognormal_lengths(n, median, sigma, lo, hi):
    """The ``n`` stratified quantiles of a log-normal, clipped."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def poisson_gaps(n, rate):
    """The ``n`` stratified quantiles of the exponential inter-arrival
    gap at ``rate`` per second, rescaled so they sum to ``n / rate``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate) / gaps.sum()


def open_loop_blocks(mix, seed, vocab, window_s):
    """A generator of ``(due_offset_s, prompt_tokens)`` for ever: a ramp
    block before offset 0, one block that exactly fills the window, then
    tail blocks of the window's own make-up. Offsets are seconds from
    the opening of the window (negative in the ramp)."""
    rng = np.random.default_rng(seed)                 # token ids
    fixed = mix.get("schedule_seed")
    order = np.random.default_rng(seed if fixed is None else fixed)
    rate = float(mix["rate_per_s"])
    ln = mix["prompt_len"]

    def block(n, start):
        lengths = lognormal_lengths(n, ln["median"], ln["sigma"],
                                    ln["min"], ln["max"])
        gaps = poisson_gaps(n, rate)
        lengths = lengths[order.permutation(n)]
        gaps = gaps[order.permutation(n)]
        # a request falls in the middle of its gap, so a block's
        # arrivals span exactly n / rate seconds
        due = start + np.cumsum(gaps) - gaps / 2
        for t, length in zip(due, lengths):
            yield float(t), rng.integers(0, vocab - 2, int(length)).tolist()

    ramp_s = float(mix["ramp_s"])
    n_ramp = max(1, int(round(rate * ramp_s)))
    yield from block(n_ramp, -n_ramp / rate)
    n_win = max(1, int(round(rate * window_s)))
    start = 0.0
    while True:
        yield from block(n_win, start)
        start += n_win / rate


def percentile(samples, q):
    """Nearest-rank percentile of exact samples (no interpolation, no
    histogram)."""
    s = sorted(samples)
    if not s:
        raise ValueError("no samples")
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]
