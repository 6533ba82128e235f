"""Data kind ``prototypes`` (the default): float inputs of the
configuration's ``input_shape`` with class labels below ``num_classes``.

A sample is its class's prototype plus noise, and a client may add a
shift of its own, so that the clients differ as writers do
(``data.client_shift`` > 0) or are IID (0).  The noise of each sample is a
row of a bank drawn once per seed: a client's data then costs the host a
gather and not a fresh normal draw, as a dataset held in memory would, so
the benchmark's own generator stays a small part of a round that meets
mostly new clients.
"""
from __future__ import annotations

import numpy as np

import traffic as tr

NOISE_ROWS = 4096


class Prototypes:
    def __init__(self, traffic, config, seed: int):
        self.seed = int(seed)
        self.input_shape = tuple(config["input_shape"])
        self.num_classes = int(config["num_classes"])
        d = int(np.prod(self.input_shape))
        g = tr.rng(self.seed, tr.STREAM_PROTOS)
        self._protos = g.standard_normal((self.num_classes, d), dtype=np.float32)
        self._bank = g.standard_normal((NOISE_ROWS, d), dtype=np.float32)
        self._bank *= np.float32(traffic["data"]["noise"])
        self._shift = float(traffic["data"]["client_shift"])

    def client(self, i: int, n: int):
        """Client ``i``'s ``n`` samples ``(x, y)``: float32 inputs, int32
        labels."""
        g = tr.rng(self.seed, tr.STREAM_CLIENT, i)
        y = g.integers(0, self.num_classes, size=n).astype(np.int32)
        x = self._bank[g.integers(0, NOISE_ROWS, size=n)]
        x += self._protos[y]
        if self._shift:
            x += np.float32(self._shift) * g.standard_normal(x.shape[1], dtype=np.float32)
        x -= x.mean()
        x /= x.std() + np.float32(1e-6)
        return x.reshape((n,) + self.input_shape), y


make = Prototypes
