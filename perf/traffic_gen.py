"""The one general generator of serving traffic. A mix is a data file of
parameters (``perf/traffic/<mix>.json``); nothing here knows a mix by
name.

Every seed gets the SAME set of (prompt, answer) sizes in another order:
the sizes are the stratified quantiles of the mix's distributions, paired
once and for all; ``--seed`` only shuffles their order and draws the
token ids. So two runs with different seeds do the same work, and the
spread between them is the system's, not the sample's.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
_PAIRING = 24   # which answer goes with which prompt: fixed, not a parameter


def _quantile(dist, q):
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    return int(min(max(round(x), dist["min"]), dist["max"]))


def size_pool(mix):
    """``pool`` (prompt, answer) pairs: the (i + 0.5) / pool quantiles of
    each distribution, paired at random (the same way for every mix and
    seed), answers cut so that prompt + answer <= ``max_total``."""
    n = mix["pool"]
    prompts = [_quantile(mix["prompt"], (i + 0.5) / n) for i in range(n)]
    answers = [_quantile(mix["answer"], (i + 0.5) / n) for i in range(n)]
    order = np.random.RandomState(_PAIRING).permutation(n)
    return [(p, max(1, min(answers[j], mix["max_total"] - p)))
            for p, j in zip(prompts, order)]


class RequestStream:
    """An endless seeded stream of ``(prompt_tokens, max_new_tokens)``:
    the pool in a seeded order, over and over, with token ids uniform
    over the published vocabulary."""

    def __init__(self, mix, vocab, seed):
        self._pool = size_pool(mix)
        self._rs = np.random.RandomState(seed % 2 ** 32)
        self._vocab = vocab
        self._order = []

    def next(self):
        if not self._order:
            self._order = list(self._rs.permutation(len(self._pool)))
        prompt_len, answer_len = self._pool[self._order.pop()]
        prompt = self._rs.randint(0, self._vocab, prompt_len)
        return prompt.astype(np.int32).tolist(), answer_len
