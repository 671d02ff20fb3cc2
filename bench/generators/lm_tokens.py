"""Synthetic language-model rows for training cells.

The benchmark's own copy of the program's ``data/synthetic.lm_batch``
(``make_token_stream``): each row is a walk of an order-1 Markov chain,
one of four chains per row, over a 64-letter alphabet lifted evenly into
the vocabulary; labels are the tokens shifted by one.  The walk is drawn
for all rows of a batch at once (one inverse-CDF draw per position), so a
round's rows cost milliseconds on the host, not a Python call per token.

Every row of every round differs: round ``r`` draws from the stream
``(seed, 1, r)``, the validation rows from ``(seed, 2)``, and the chains
themselves from ``(seed, 0)``.
"""

from __future__ import annotations

import numpy as np

ALPHABET = 64
CHAINS = 4


class LMTokens:
    def __init__(self, vocab: int, seed: int, np_rng):
        self.vocab = vocab
        self._rng = np_rng
        self._seed = seed
        k = min(vocab, ALPHABET)
        trans = np_rng(seed, 0).dirichlet(np.ones(k) * 0.3,
                                          size=(CHAINS, k))
        self._cdf = np.cumsum(trans, axis=-1)
        self._cdf[..., -1] = 1.0
        self._lift = (np.arange(k) * max(vocab // k, 1)) % vocab
        self.k = k

    def rows(self, n: int, length: int, *path: int) -> np.ndarray:
        """(n, length) token ids of n independent walks."""
        rng = self._rng(self._seed, *path)
        chain = rng.integers(0, CHAINS, size=n)
        state = rng.integers(0, self.k, size=n)
        u = rng.random((length, n))
        out = np.empty((n, length), np.int32)
        for t in range(length):
            cdf = self._cdf[chain, state]                     # (n, k)
            state = np.minimum((cdf < u[t, :, None]).sum(-1), self.k - 1)
            out[:, t] = state
        return self._lift[out].astype(np.int32)

    def lm_batch(self, n: int, seq: int, *path: int) -> dict:
        toks = self.rows(n, seq + 1, *path)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def round_batch(gen: LMTokens, traffic: dict, r: int) -> dict:
    """Round ``r``'s rows as (clients, rows per client, seq)."""
    n, b, s = traffic["clients"], traffic["rows_per_client"], traffic["seq"]
    d = gen.lm_batch(n * b, s, 1, r)
    return {k: v.reshape(n, b, s) for k, v in d.items()}


def val_batch(gen: LMTokens, traffic: dict) -> dict:
    return gen.lm_batch(traffic["val_rows"], traffic["seq"], 2)
