"""The general load generators.  A traffic mix is a data file of
parameters under ``perf/traffic/``; this module turns one into inputs.

Sizes never come from ``--seed``: the lengths are the quantiles of the
file's distributions (no draw at all) and their order is the file's
(``order_seed``); ``--seed`` fills in the token ids (and, in the driver,
the weights).  Said plainly: every run of a closed-loop cell replays ONE
trajectory of the scheduler, and its tails are the tails of that one
sample of requests (about 80 in a 20 s window of ``gpt2m.serve_closed``).

Why: a closed loop with fixed output lengths evolves deterministically in
engine steps, and which lengths follow which decides the queue every
request meets.  With the order drawn from ``--seed``, three chip runs
spread ``serve_tokens_per_s`` by 5.7% and ``ttft_p95_ms`` by 24% at a
step time steady to 0.3% (PR 24); a replay of the scheduler in steps
gives the same 19-23% between orders at a window 2.5 times as long, so
it is the order and not the sample's size, and no bound the contract
allows (10%) holds it.  Under the file's order the replay's tail is 66
steps for any window of 200 to 280 steps and any ramp of 260 to 340
(61-63 steps from 500 to 2,300): a change to the step TIME moves the
tail in proportion; a change to the SCHEDULER moves the loop to another
trajectory, whose tail differs by a fifth by chance alone (PERF.md
sections 6 and 7).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths: the ``(i + 0.5) / n`` quantiles of a lognormal with
    this median and sigma, clipped to ``[lo, hi]``."""
    z = NormalDist()
    q = [median * math.exp(sigma * z.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), lo, hi).astype(np.int64)


def request_pool(traffic: dict) -> list[tuple[int, int]]:
    """The cell's fixed multiset of ``(prompt_len, output_len)`` pairs.
    Prompt and output quantiles are paired by a fixed shuffle, the same
    for every seed."""
    n = traffic["pool_requests"]
    p, o = traffic["prompt_len"], traffic["output_len"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    pairing = np.random.default_rng(traffic["pairing_seed"]).permutation(n)
    return [(int(a), int(b)) for a, b in zip(prompts, outputs[pairing])]


class RequestStream:
    """Requests in the file's order, cycling through the pool: prompt
    token ids uniform over the vocabulary from the seed, output length
    fixed (no EOS)."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.pool = request_pool(traffic)
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(
            traffic["order_seed"]).permutation(len(self.pool))
        self.vocab = vocab
        self.i = 0

    def next(self) -> tuple[np.ndarray, int]:
        plen, olen = self.pool[self.order[self.i % len(self.pool)]]
        self.i += 1
        return (self.rng.integers(0, self.vocab, size=plen, dtype=np.int32),
                olen)


def token_batch(rng: np.random.Generator, vocab: int, batch: int,
                seq_len: int) -> np.ndarray:
    """``(batch, seq_len + 1)`` token ids uniform over the vocabulary:
    inputs are ``[:, :-1]``, next-token targets ``[:, 1:]``."""
    return rng.integers(0, vocab, size=(batch, seq_len + 1), dtype=np.int32)
