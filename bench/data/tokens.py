"""Data kind ``tokens``: int32 sequences of ``config["seq_len"]`` token ids
below ``config["vocab"]`` (under a sliced vocabulary, the slice), for a
next-token loss; ``y`` is a copy of ``x``, and the loss shifts it.

Text comes from ``TOPICS`` topics.  Each topic is a first-order Markov
chain over the vocabulary: the token after ``c`` lies ``k`` places after
``c`` in a permutation that is the topic's own, where ``k`` follows a Zipf
law of exponent ``ZIPF`` over ``1 .. vocab - 1``: about 1, as word
frequencies in natural text follow it (Zipf 1949; Piantadosi 2014,
"Zipf's word frequency law in natural language", Psychon. Bull. Rev.).
The next token is then predictable from the current one and the topic, so
next-token prediction has a signal to learn.  A bank of ``BANK_ROWS``
sequences, shared among the topics, is drawn once per seed, as the
``prototypes`` kind draws its noise bank.  A client holds documents of the
topics in the shares of its own Dirichlet draw (``data.topic_alpha``, the
one setting of this kind; small is non-IID by document), and its data is
a gather of bank rows.
"""
from __future__ import annotations

import numpy as np

import traffic as tr

TOPICS = 8
ZIPF = 1.0
BANK_ROWS = 4096


class Tokens:
    def __init__(self, traffic, config, seed: int):
        self.seed = int(seed)
        self.seq_len = int(config["seq_len"])
        self.num_classes = vocab = int(config["vocab"])
        self.alpha = float(traffic["data"]["topic_alpha"])
        rows = BANK_ROWS // TOPICS
        if vocab < 2:
            raise ValueError("a token vocabulary has at least 2 ids")
        g = tr.rng(self.seed, tr.STREAM_PROTOS)
        perm = np.stack([g.permutation(vocab) for _ in range(TOPICS)])
        where = np.argsort(perm, axis=1)                 # position of each id
        zipf = np.cumsum(np.arange(1, vocab, dtype=np.float64) ** -ZIPF)
        zipf /= zipf[-1]
        topic = np.repeat(np.arange(TOPICS), rows)
        bank = np.empty((TOPICS * rows, self.seq_len), np.int32)
        c = g.integers(0, vocab, size=len(bank))
        for t in range(self.seq_len):
            bank[:, t] = c
            k = 1 + np.searchsorted(zipf, g.random(len(bank)), side="right")
            c = perm[topic, (where[topic, c] + k) % vocab]
        self._bank = bank.reshape(TOPICS, rows, self.seq_len)

    def client(self, i: int, n: int):
        """Client ``i``'s ``n`` sequences ``(x, y)``, both int32 of shape
        ``(n, seq_len)``."""
        g = tr.rng(self.seed, tr.STREAM_CLIENT, i)
        shares = g.dirichlet(np.full(TOPICS, self.alpha))
        topic = g.choice(TOPICS, size=n, p=shares)
        x = self._bank[topic, g.integers(0, self._bank.shape[1], size=n)]
        return x, x.copy()


make = Tokens
