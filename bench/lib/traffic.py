"""The one traffic generator: a federation drawn from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) gives the population, each
client's sample count, the cohort size, the local schedule and how the data
is drawn.  The same seed gives the same clients, data and cohorts; another
seed gives the same sizes in another draw.  Nothing here imports the
program: the program and the reference are both fed from this module.

``data.kind`` names a data kind, ``data/<kind>.py`` beside ``lib/``
(``prototypes`` where it is absent): its ``make(traffic, config, seed)``
gives ``num_classes`` and ``client(i, n)``, client ``i``'s ``n`` samples.
``samples_per_client`` is a number, or a distribution of client sizes
(:func:`client_sizes`).
"""
from __future__ import annotations

import re
from statistics import NormalDist
from typing import Any, Dict, List, Sequence

import numpy as np

from spec import BENCH_DIR, SpecError, load_module

STREAM_PROTOS, STREAM_CLIENT, STREAM_COHORT, STREAM_WEIGHTS = 1, 2, 3, 4
STREAM_FROZEN, STREAM_SIZES = 5, 6
DATA_DIR = BENCH_DIR / "data"


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; seeds may exceed 64 bits."""
    s = int(seed)
    return np.random.default_rng([s % 2**64, (s // 2**64) % 2**64, *stream])


def client_id(i: int) -> str:
    return f"w{i:05d}"


def data_kind(name: str):
    """The module of data kind ``name``."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise SpecError(f"no data kind {name!r}")
    return load_module(DATA_DIR / f"{name}.py")


def client_sizes(spec: Any, population: int, seed: int) -> np.ndarray:
    """Every client's sample count.  A number gives every client that
    many.  ``{"lognormal": {"mean": m, "sigma": s}, "min": a, "max": b}``
    gives the population the lognormal's quantiles at ``(k + 0.5) /
    population`` (``m`` and ``s`` of the logarithm), rounded and held to
    ``[a, b]``, dealt to the clients by a permutation of a stream of their
    own: every seed then has the same set of sizes in another order, so
    the same work."""
    if not isinstance(spec, dict):
        return np.full(population, int(spec), np.int64)
    if set(spec) != {"lognormal", "min", "max"}:
        raise SpecError(f"samples_per_client: unknown distribution {sorted(spec)}")
    ln = spec["lognormal"]
    dist = NormalDist(float(ln["mean"]), float(ln["sigma"]))
    logs = np.asarray([dist.inv_cdf((k + 0.5) / population) for k in range(population)])
    sizes = np.clip(np.rint(np.exp(logs)), int(spec["min"]), int(spec["max"])).astype(np.int64)
    return sizes[rng(seed, STREAM_SIZES).permutation(population)]


class Federation:
    """Clients, their data and the cohort of every round, from one seed."""

    def __init__(self, traffic: Dict[str, Any], config: Dict[str, Any], seed: int):
        self.seed = int(seed)
        self.population = int(traffic["population"])
        if self.population > 99999:
            raise ValueError("client ids hold at most 99999 clients")
        self.cohort = int(traffic["clients_per_round"])
        self.batch_size = int(traffic["batch_size"])
        self.local_epochs = int(traffic["local_epochs"])
        self._data = data_kind(traffic["data"].get("kind", "prototypes")).make(
            traffic, config, self.seed)
        self.num_classes = self._data.num_classes
        self._order = rng(self.seed, STREAM_COHORT).permutation(self.population)
        self._sizes = client_sizes(traffic["samples_per_client"], self.population, self.seed)

    def size(self, i: int) -> int:
        return int(self._sizes[i])

    def max_size(self) -> int:
        return int(self._sizes.max())

    def data(self, i: int):
        """Client ``i``'s ``(x, y)``, as its data kind draws them."""
        return self._data.client(i, self.size(i))

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
