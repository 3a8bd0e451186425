"""A second open-loop generator beside ``traffic_gen.open_loop_blocks``:
the same contract (a ramp block before offset 0, a block that exactly
fills the window, tail blocks of the window's own make-up; lengths and
gaps the stratified quantiles of their distributions, their ORDER drawn
from the mix's ``schedule_seed``, token ids from ``--seed``) for a mix
whose prompt lengths are a MIXTURE: short and long requests in one
queue.

``mix["prompt_len"]`` holds ``short`` (a log-normal: ``median``,
``sigma``, ``min``, ``max``), ``long`` (a uniform: ``min``, ``max``)
and ``long_share``: of every block's ``n`` requests ``round(long_share
* n)`` are long.
"""

import numpy as np

from chipbench import traffic_gen


def mixture_lengths(n, spec):
    """The ``n`` lengths of one block: stratified quantiles of each
    component, the long ones last (the caller permutes)."""
    n_long = int(round(spec["long_share"] * n))
    short, long_ = spec["short"], spec["long"]
    lengths = traffic_gen.lognormal_lengths(
        n - n_long, short["median"], short["sigma"], short["min"],
        short["max"]) if n > n_long else np.zeros((0,), int)
    q = (np.arange(n_long) + 0.5) / max(n_long, 1)
    longs = np.rint(long_["min"] + q * (long_["max"] - long_["min"]))
    return np.concatenate([lengths, longs.astype(int)])


def open_loop_blocks(mix, seed, vocab, window_s):
    """``(due_offset_s, prompt_tokens)`` for ever; see the module's
    docstring and ``traffic_gen.open_loop_blocks``."""
    rng = np.random.default_rng(seed)                 # token ids
    fixed = mix.get("schedule_seed")
    order = np.random.default_rng(seed if fixed is None else fixed)
    rate = float(mix["rate_per_s"])

    def block(n, start):
        lengths = mixture_lengths(n, mix["prompt_len"])
        gaps = traffic_gen.poisson_gaps(n, rate)
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
