"""The one traffic generator: reads a mix's parameters, yields its work.

Every seed gets the same sizes in the same order, so runs with different
seeds do the same work and differ in their token ids: requests come in
blocks of ``block``, each block holds the lengths at the ``block``
mid-quantiles ``(i + 0.5) / block`` of the mix's distribution, and the
order within a block is drawn from the mix's own ``order_seed``. (With
one-token prefill, a window holds about one request per lane, so an order
drawn from ``--seed`` swung the tokens served in a window by 2x between
seeds.)
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one stream of one seed (any size of seed)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def lognormal_grid(dist: dict, n: int) -> np.ndarray:
    """Lengths at the n mid-quantiles of a lognormal given by its median and
    sigma, rounded and clipped to ``[min, max]``."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


class ChatRequests:
    """Closed-loop chat requests: ``next()`` gives (prompt ids, max_new).

    ``mix`` keys: ``prompt`` and ``output`` (lognormal ``median``, ``sigma``,
    ``min``, ``max``), ``max_total`` (prompt + output), ``block``. Token ids
    are uniform over ``[0, vocab)``; there is no EOS.
    """

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.block = int(mix["block"])
        self._prompts = lognormal_grid(mix["prompt"], self.block)
        self._outputs = lognormal_grid(mix["output"], self.block)
        self._order = rng_for(mix["order_seed"], 10)
        self._tokens = rng_for(seed, 11)
        self._queue: list[tuple[int, int]] = []
        self.issued = 0

    def lengths(self) -> tuple[int, int]:
        """The next request's (prompt length, output length)."""
        if not self._queue:
            p = self._order.permutation(self._prompts)
            o = self._order.permutation(self._outputs)
            o = np.minimum(o, self.mix["max_total"] - p)
            self._queue = list(zip(p.tolist(), o.tolist()))
        return self._queue.pop(0)

    def next(self) -> tuple[np.ndarray, int]:
        p, o = self.lengths()
        self.issued += 1
        ids = self._tokens.integers(0, self.vocab, p, dtype=np.int32)
        return ids, int(o)


def dephase_starts(n_lanes: int, steps: int) -> list[int]:
    """The set-up step at which each lane takes its first request, evenly
    spread over ``steps`` steps, so that the window starts with the lanes
    out of phase: some in prefill, some decoding."""
    return [math.floor(i * steps / n_lanes) for i in range(n_lanes)]
