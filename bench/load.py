"""Traffic from a mix's parameters and the seed.

Every seed gets the same multiset of sizes, in another order: each cycle of
``cycle`` requests takes the prompt and output lengths at the quantiles
``(i + 0.5) / cycle`` of their distributions, and the seed only shuffles
them and draws the token ids. So two seeds offer the same work, and a seed
does not change how much there is.

Kind ``offline_batch``: no arrival times; the window keeps ``queue_rows``
x ``max_batch`` requests waiting so the pool never runs dry.
"""
from __future__ import annotations

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """Integer sizes at the ``n`` mid-quantiles of ``dist``, clipped to
    ``[lo, hi]``."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] != "uniform":
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    x = dist["lo"] + u * (dist["hi"] + 1 - dist["lo"])
    return np.clip(np.floor(x), dist["lo"], dist["hi"]).astype(np.int64)


class Traffic:
    """Iterator of ``(prompt, max_new)``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.kind = mix["kind"]
        if self.kind != "offline_batch":
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        n = self.cycle = int(mix["cycle"])
        self._plen = quantiles(mix["prompt"], n)
        self._olen = quantiles(mix["output"], n)
        self._i = 0
        self._order = None

    def _rng(self, *stream) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def __iter__(self):
        return self

    def __next__(self):
        c, j = divmod(self._i, self.cycle)
        if j == 0:
            r = self._rng(1, c)
            self._order = (r.permutation(self.cycle),
                           r.permutation(self.cycle))
        po, oo = self._order
        plen, max_new = int(self._plen[po[j]]), int(self._olen[oo[j]])
        prompt = self._rng(2, self._i).integers(
            0, self.vocab, plen).astype(np.int32)
        self._i += 1
        return prompt, max_new

    def longest(self) -> int:
        """Most KV slots one request of this mix can need."""
        return int(self._plen.max() + self._olen.max())

    def prompt_buckets(self, minimum: int = 8) -> list[int]:
        """The power-of-two prompt buckets an admission wave can take."""
        lo = max(minimum, int(self._plen.min()))
        hi = int(self._plen.max())
        b = 1 << (lo - 1).bit_length()
        out = []
        while True:
            out.append(b)
            if b >= hi:
                return out
            b *= 2
