"""The one traffic generator: a federation drawn from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) gives the population, each
client's sample count, the cohort size, the local schedule and how the data
is drawn.  The same seed gives the same clients, data and cohorts; another
seed gives the same sizes in another draw.  Nothing here imports the
program: the program and the reference are both fed from this module.

Data are class prototypes plus a per-client shift plus noise, so that the
clients differ as writers do (``client_shift`` > 0) or are IID (0).  The
noise of each sample is a row of a bank drawn once per seed: a client's
data then costs the host a gather and not a fresh normal draw, as a
dataset held in memory would, so the benchmark's own generator stays a
small part of a round that meets mostly new clients.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

STREAM_PROTOS, STREAM_CLIENT, STREAM_COHORT, STREAM_WEIGHTS = 1, 2, 3, 4
NOISE_ROWS = 4096


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; seeds may exceed 64 bits."""
    s = int(seed)
    return np.random.default_rng([s % 2**64, (s // 2**64) % 2**64, *stream])


def client_id(i: int) -> str:
    return f"w{i:05d}"


class Federation:
    """Clients, their data and the cohort of every round, from one seed."""

    def __init__(self, traffic: Dict[str, Any], config: Dict[str, Any], seed: int):
        self.t = traffic
        self.seed = int(seed)
        self.input_shape = tuple(config["input_shape"])
        self.num_classes = int(config["num_classes"])
        self.population = int(traffic["population"])
        if self.population > 99999:
            raise ValueError("client ids hold at most 99999 clients")
        self.cohort = int(traffic["clients_per_round"])
        self.batch_size = int(traffic["batch_size"])
        self.local_epochs = int(traffic["local_epochs"])
        d = int(np.prod(self.input_shape))
        g = rng(self.seed, STREAM_PROTOS)
        self._protos = g.standard_normal((self.num_classes, d), dtype=np.float32)
        self._bank = g.standard_normal((NOISE_ROWS, d), dtype=np.float32)
        self._bank *= np.float32(traffic["data"]["noise"])
        self._shift = float(traffic["data"]["client_shift"])
        self._order = rng(self.seed, STREAM_COHORT).permutation(self.population)

    def size(self, i: int) -> int:
        return int(self.t["samples_per_client"])

    def data(self, i: int):
        """Client ``i``'s ``(x, y)``: float32 inputs of the configuration's
        shape, int32 labels."""
        n = self.size(i)
        g = rng(self.seed, STREAM_CLIENT, i)
        y = g.integers(0, self.num_classes, size=n).astype(np.int32)
        x = self._bank[g.integers(0, NOISE_ROWS, size=n)]
        x += self._protos[y]
        if self._shift:
            x += np.float32(self._shift) * g.standard_normal(x.shape[1], dtype=np.float32)
        x -= x.mean()
        x /= x.std() + np.float32(1e-6)
        return x.reshape((n,) + self.input_shape), y

    def cohort_of(self, round_id: int) -> List[int]:
        """The clients of round ``round_id``: the next ``clients_per_round``
        of a seeded permutation of the population, taken in turn, so every
        client is drawn once before any is drawn again (sampling without
        replacement across rounds).  Each round then meets the same number
        of clients it has not met lately: the same work in every round of
        every seed."""
        start = round_id * self.cohort
        return [int(self._order[(start + j) % self.population]) for j in range(self.cohort)]

    def samples_of(self, clients: Sequence[int]) -> int:
        """Real training samples a round trains: each client's own data
        times its local epochs, with no padding."""
        return sum(self.size(i) for i in clients) * self.local_epochs


def jax_key(seed: int, stream: int):
    """A JAX key for one stream of a seed of any size."""
    import jax

    s = int(seed) % 2**64
    key = jax.random.PRNGKey(s % 2**31)
    key = jax.random.fold_in(key, s // 2**31 % 2**31)
    key = jax.random.fold_in(key, s // 2**62)
    return jax.random.fold_in(key, stream)


def stable_hash(s: str) -> int:
    """FNV-1a over the id's bytes, mod 2**31: the per-client part of
    EasyFL's local shuffle seed."""
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 % (2**31)
    return h


def local_batches(n: int, batch_size: int, seed: int) -> np.ndarray:
    """One epoch's batch indices, as EasyFL's client trains them: a
    permutation from ``RandomState(seed)``, the last batch wrapping to the
    start so every batch is full."""
    idx = np.random.RandomState(seed).permutation(n)
    n_batches = max(1, -(-n // batch_size))
    padded = np.concatenate([idx, idx[: (-len(idx)) % batch_size or 0]])
    if len(padded) < n_batches * batch_size:
        reps = -(-n_batches * batch_size // n)
        padded = np.tile(idx, reps)[: n_batches * batch_size]
    return padded.reshape(n_batches, batch_size)


def client_schedule(fed: Federation, i: int, round_id: int) -> np.ndarray:
    """All of client ``i``'s batches in round ``round_id``: (steps, batch).
    Epoch ``e`` shuffles with seed ``round * 9973 + hash(id) + e``."""
    seed = round_id * 9973 + stable_hash(client_id(i))
    return np.concatenate([local_batches(fed.size(i), fed.batch_size, seed + e)
                           for e in range(fed.local_epochs)]).astype(np.int32)
