"""The one traffic generator: closed-loop sessions from a mix's parameters.

A mix file gives `sessions` (clients, each with one request in flight and
zero think time), the `prompt` and `answer` length distributions, and the
serving shape (`lanes`, `max_seq`, `prefill_chunk`). A length distribution
is a lognormal (`median`, log-space `sigma`) truncated to [`min`, `max`];
the mix's `about` names the published statistics it follows.

Lengths are not drawn per seed: every seed serves the same stratified deck
of lengths (the distribution's quantiles at the midpoints of `deck` equal
slices of its probability), dealt to the sessions in one fixed shuffled
order, so a run's window holds the same work whatever the seed; the seed
draws the token ids (and the run's weights).

A session's first request starts part-way through a request's life, with
a stratified share of the deck's mean life left: sessions then start out
of phase, and no synchronised prefill of every lane opens the run.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

#: the one shuffle of the length decks and residual phases, for every seed
ORDER_SEED = 20250330


def deck(dist: dict, n: int) -> list:
    """n lengths of a truncated lognormal: its quantiles at the midpoints of
    n equal slices of the probability between `min` and `max`."""
    z, mu, s = NormalDist(), math.log(dist["median"]), dist["sigma"]
    lo = z.cdf((math.log(dist["min"]) - mu) / s)
    hi = z.cdf((math.log(dist["max"]) - mu) / s)
    out = []
    for j in range(n):
        u = lo + (hi - lo) * (j + 0.5) / n
        x = round(math.exp(mu + s * z.inv_cdf(u)))
        out.append(int(min(max(x, dist["min"]), dist["max"])))
    return out


class Sessions:
    """Per-session request streams of a mix; `first(s)` then `next(s)`."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        order = np.random.default_rng(ORDER_SEED)
        n, k = mix["sessions"], mix["deck"]
        self.prompts = [list(order.permutation(deck(mix["prompt"], k)))
                        for _ in range(n)]
        self.answers = [list(order.permutation(deck(mix["answer"], k)))
                        for _ in range(n)]
        self.phase = list(order.permutation([(s + 0.5) / n
                                             for s in range(n)]))
        self.dealt = [0] * n
        chunk = mix["prefill_chunk"]
        #: a request's life in ticks (one per prompt chunk, one per answer
        #: token), averaged over the deck
        self.life = (sum(math.ceil(p / chunk) for p in self.prompts[0])
                     + sum(self.answers[0])) / k

    def _tokens(self, n: int) -> list:
        return [int(t) for t in self.rng.integers(1, self.vocab, n)]

    def _lengths(self, s: int) -> tuple:
        i = self.dealt[s] % self.mix["deck"]
        self.dealt[s] += 1
        return int(self.prompts[s][i]), int(self.answers[s][i])

    def first(self, s: int) -> tuple:
        """(prompt tokens, answer length) of session s's first request: the
        session's first request of the deck, entered with its stratified
        share of the deck's mean life left (in ticks), so the sessions'
        first requests end apart. A point inside the answer leaves a
        one-token prompt."""
        _, a = self._lengths(s)
        chunk = self.mix["prefill_chunk"]
        left = max(1, int(round(self.phase[s] * self.life)))
        if left <= a:
            return self._tokens(1), left
        return self._tokens(min(self.mix["prompt"]["max"],
                                (left - a) * chunk)), a

    def next(self, s: int) -> tuple:
        p, a = self._lengths(s)
        return self._tokens(p), a
