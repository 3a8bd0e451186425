"""A third open-loop generator beside ``traffic_gen.open_loop_blocks``
and ``traffic_mixed``: the same contract (a ramp block before offset 0,
a block that exactly fills the window, tail blocks of the window's own
make-up; lengths and gaps the stratified quantiles of their
distributions, their ORDER drawn from the mix's ``schedule_seed``,
token ids from ``--seed``) for a mix whose arrivals come in BURSTS.

Inter-arrival gaps are gamma-distributed about the mean rate with
shape ``mix["gap_shape"]``: the coefficient of variation is ``shape **
-0.5``, 2 at the 0.25 BurstGPT fits to production chat traffic
(arXiv:2401.17644), where the exponential gaps of a Poisson process
have 1. Most gaps are then far shorter than the mean and a few far
longer: requests arrive in clumps with lulls between them, at the
same requests a second. Prompt lengths are ``traffic_gen``'s clipped
log-normal.
"""

import numpy as np
from scipy.special import gammaincinv

from chipbench import traffic_gen


def gamma_gaps(n, rate, shape):
    """The ``n`` stratified quantiles of the gamma inter-arrival gap of
    ``shape`` at ``rate`` per second, rescaled so they sum to ``n /
    rate``."""
    q = (np.arange(n) + 0.5) / n
    gaps = gammaincinv(shape, q)
    return gaps * (n / rate) / gaps.sum()


def open_loop_blocks(mix, seed, vocab, window_s):
    """``(due_offset_s, prompt_tokens)`` for ever; see the module's
    docstring and ``traffic_gen.open_loop_blocks``."""
    rng = np.random.default_rng(seed)                 # token ids
    fixed = mix.get("schedule_seed")
    order = np.random.default_rng(seed if fixed is None else fixed)
    rate, shape = float(mix["rate_per_s"]), float(mix["gap_shape"])
    ln = mix["prompt_len"]

    def block(n, start):
        lengths = traffic_gen.lognormal_lengths(
            n, ln["median"], ln["sigma"], ln["min"], ln["max"])
        gaps = gamma_gaps(n, rate, shape)
        lengths = lengths[order.permutation(n)]
        gaps = gaps[order.permutation(n)]
        due = start + np.cumsum(gaps) - gaps / 2
        for t, length in zip(due, lengths):
            yield float(t), rng.integers(0, vocab - 2, int(length)).tolist()

    n_ramp = max(1, int(round(rate * float(mix["ramp_s"]))))
    yield from block(n_ramp, -n_ramp / rate)
    n_win = max(1, int(round(rate * window_s)))
    start = 0.0
    while True:
        yield from block(n_win, start)
        start += n_win / rate
