"""The one general traffic generator: a mix is a data file, never code.

Two kinds of mix. ``serve``: an open-loop schedule of requests (due time,
prompt, output budget, greedy or sampled). ``train``: a stream of batches of
token ids. Everything is drawn from the run's seed, and every seed gets the
SAME multiset of sizes and arrival gaps in another order: the sizes are the
quantiles of the mix's distributions, not draws, so that two seeds do the
same work and differ only in its order and in the token ids.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class ServeRequest:
    due: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    greedy: bool
    seed: int  # the request's own sample-stream seed
    shared: int  # index of the system prompt it begins with, or -1


def _lognormal_quantiles(n, median, sigma, lo, hi):
    """The n mid-quantiles of a lognormal, clipped: a fixed multiset."""
    nd = NormalDist()
    q = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), lo, hi).astype(np.int64)


def _exp_gap_quantiles(n, total):
    """The n mid-quantiles of an exponential (Poisson arrivals), scaled so
    that they add up to ``total`` seconds."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (total / g.sum())


def serve_window(mix: dict, seed: int, seconds: float, vocab: int,
                 system_prompts) -> list:
    """The requests due inside a window ``[0, seconds)``:
    ``round(rate * seconds)`` of them, whose sizes and gaps are the same for
    every seed."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    p, o = mix["prompt_len"], mix["output_len"]
    prompt_len = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                      p["max"])
    out_len = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                   o["max"])
    # the pairing of prompt and output lengths belongs to the mix, not to
    # the seed: one fixed shuffle
    out_len = out_len[np.random.default_rng(n).permutation(n)]
    share = mix.get("shared_prefix", {})
    n_sys, sys_len = len(system_prompts), share.get("tokens", 0)
    every = int(round(1.0 / share["share"])) if share.get("share") else 0
    greedy_every = int(round(1.0 / mix["greedy_share"]))
    rng = np.random.default_rng([int(seed), 1])
    order = rng.permutation(n)
    gaps = _exp_gap_quantiles(n, seconds)[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0] * rng.random()  # first is due early
    reqs = []
    for slot, i in enumerate(order):
        # quantile index i decides the kind, so every kind spans all sizes
        shared = ((i // (every * greedy_every)) % n_sys
                  if every and i % every == 0 else -1)
        t0 = int(prompt_len[i])
        if shared >= 0:
            t0 = max(t0, sys_len + mix["prompt_len"]["min"])
        prompt = rng.integers(1, vocab, t0, dtype=np.int64).astype(np.int32)
        if shared >= 0:
            prompt[:sys_len] = system_prompts[shared]
        reqs.append(ServeRequest(
            due=float(max(due[slot], 0.0)), prompt=prompt,
            max_new_tokens=int(out_len[i]),
            greedy=(i // max(every, 1)) % greedy_every == 0,
            seed=int(rng.integers(0, 2**31 - 1)), shared=int(shared)))
    return reqs


def system_prompts(mix: dict, seed: int, vocab: int) -> list:
    share = mix.get("shared_prefix", {})
    rng = np.random.default_rng([int(seed), 7])
    return [rng.integers(1, vocab, share["tokens"], dtype=np.int64)
            .astype(np.int32) for _ in range(share.get("prompts", 0))]


class TrainBatches:
    """A new batch of token ids every step, made on the host from the seed.
    ``causal_lm``: (ids[:, :-1], ids[:, 1:]); ``classify``: (ids, labels)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng([int(seed), 11])

    def next(self):
        b, t = self.mix["batch"], self.mix["seq"]
        if self.mix["task"] == "causal_lm":
            ids = self.rng.integers(0, self.vocab, (b, t + 1), dtype=np.int64)
            return (ids[:, :-1].astype(np.int32),
                    ids[:, 1:].astype(np.int32))
        ids = self.rng.integers(1, self.vocab, (b, t), dtype=np.int64)
        return ids.astype(np.int32), self._labels(b)

    def _labels(self, b):
        """``label_shares``: the share of a batch's rows that carry each
        class. Every batch holds the same count of each, in an order drawn
        from the seed: with as many rows of one class as of the other, the
        gradient of a fresh classifier is a sum that all but cancels, and
        how nearly is the luck of the seed (PERF.md section 6)."""
        shares = np.asarray(self.mix["label_shares"], float)
        counts = np.floor(b * shares / shares.sum()).astype(int)
        counts[np.argmax(shares)] += b - counts.sum()
        labels = np.repeat(np.arange(len(shares)), counts)
        return self.rng.permutation(labels).astype(np.int32)

    @property
    def tokens_per_step(self):
        return self.mix["batch"] * self.mix["seq"]
