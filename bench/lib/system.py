"""The system under test, built as ``easyfl.run`` builds it.

``core/api.py`` folds the user's configuration into a ``Config``, picks the
registered model, builds the server and a ``Trainer`` over the federated
data, and calls ``Trainer.run``.  Here the same objects are built from the
cell's files, with two things taken from the benchmark instead of the
defaults: the cohort of every round (a server whose ``selection`` reads
the traffic's draw) and the starting weights (made from the seed).  The
timed entry is ``Trainer.run_round``.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, List

import jax

import traffic as tr

# rounds a Trainer is told it will run: what decides how many clients' rows
# it keeps on the device (every client of the population, for these cells)
ROUNDS_TOLD = 1_000_000


class LazyClients:
    """``clients`` mapping of a federation: each client's data is drawn
    when the system first asks for it, as a virtual population's is."""

    def __init__(self, fed: tr.Federation):
        from repro.data.fed_data import ClientData

        self._fed = fed
        self._cls = ClientData
        self._ids = [tr.client_id(i) for i in range(fed.population)]
        self._index = {c: i for i, c in enumerate(self._ids)}

    def __getitem__(self, cid: str):
        x, y = self._fed.data(self._index[cid])
        return self._cls(x, y)

    def __contains__(self, cid) -> bool:
        return cid in self._index

    def __len__(self) -> int:
        return len(self._ids)

    def __iter__(self) -> Iterator[str]:
        return iter(self._ids)


def program_config(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The ``easyfl.init`` dictionary of a cell."""
    opt = traffic["optimizer"]
    return {
        "model": config["program_model"],
        "seed": int(seed) % 2**31,
        "data": {"batch_size": traffic["batch_size"]},
        "server": {"rounds": ROUNDS_TOLD, "clients_per_round": traffic["clients_per_round"],
                   "test_every": 0},
        "client": {"local_epochs": traffic["local_epochs"], "optimizer": opt["name"],
                   "lr": opt["lr"], "momentum": opt["momentum"],
                   "compression": traffic["compression"],
                   **({"stc_sparsity": traffic["stc_keep"]}
                      if traffic["compression"] == "stc" else {})},
        "resources": {"execution": "batched",
                      "aggregation_kernel": bool(traffic["aggregation_kernel"]),
                      "distributed": traffic["distributed"]},
    }


def build(config: Dict[str, Any], traffic: Dict[str, Any], fed: tr.Federation,
          weights: Any, seed: int):
    """A ``Trainer`` for the cell, holding ``weights`` as its global model."""
    from repro.core.config import Config
    from repro.core.rounds import Trainer
    from repro.core.server import Server
    from repro.data.fed_data import ClientData, FederatedDataset
    from repro.models.registry import get_model

    class CohortServer(Server):
        """EasyFL's server with the traffic's cohorts as its selection."""

        def selection(self, client_ids, round_id: int) -> List[str]:
            return [tr.client_id(i) for i in fed.cohort_of(round_id)]

    cfg = Config.make(program_config(config, traffic, seed))
    model = get_model(cfg.model)
    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                  jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    have = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    if want != have:
        raise ValueError(f"{cfg.model}: the system's parameters are not the "
                         f"configuration's: {want} != {have}")
    x0, y0 = fed.data(0)
    fed_data = FederatedDataset(clients=LazyClients(fed), test=ClientData(x0[:1], y0[:1]),
                                num_classes=fed.num_classes)
    server = CohortServer(model, cfg, fed_data.test)
    trainer = Trainer(cfg, model, fed_data, server=server)
    trainer.server.params = weights
    return trainer


def data_pool_inserts(trainer) -> float:
    """Rows the executor's device data pool has inserted so far (NaN when
    the system keeps no pool)."""
    pool = getattr(getattr(trainer, "engine", None), "_pool", None)
    stats = getattr(pool, "stats", None)
    return float(stats["inserts"]) if stats else float("nan")


def host_params(trainer):
    return jax.device_get(trainer.server.params)


def block(trainer) -> None:
    jax.block_until_ready(trainer.server.params)
