"""The system under test, built as ``easyfl.run`` builds it.

``core/api.py`` folds the user's configuration into a ``Config``, picks the
registered model, builds the server and a ``Trainer`` over the federated
data, and calls ``Trainer.run``.  Here the same objects are built from the
cell's files, with two things taken from the benchmark instead of the
defaults: the cohort of every round (a server whose ``selection`` reads
the traffic's draw) and the starting weights (made from the seed).  Where
the configuration has a frozen tree (the base under LoRA), that too is
made from the seed and handed to the program.  A configuration's
``"program"`` object is folded into the ``easyfl.init`` dictionary.  The
timed entry is ``Trainer.run_round``.
"""
from __future__ import annotations

import functools
import gc
import sys
import weakref
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


def _deep_merge(base: Dict[str, Any], extra: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``extra`` folded in, object by object."""
    out = dict(base)
    for k, v in extra.items():
        out[k] = (_deep_merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def program_config(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The ``easyfl.init`` dictionary of a cell: the traffic's settings,
    then the configuration's ``"program"`` object folded in."""
    opt = traffic["optimizer"]
    return _deep_merge({
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
    }, config.get("program", {}))


def _tree_type(tree) -> Any:
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def _check_tree(what: str, want, have) -> None:
    if _tree_type(want) != _tree_type(have):
        raise ValueError(f"{what}: the system's tree is not the configuration's: "
                         f"{_tree_type(want)} != {_tree_type(have)}")


def _handing(model, frozen):
    """``model``, whose ``init`` hands out ``frozen`` once: under LoRA the
    ``Trainer`` asks its model's ``init`` for the base that it freezes.  A
    second call is an error: the program would then hold a base that is not
    the seed's."""
    handed = [frozen]

    class Handed(type(model)):
        def init(self, key):
            if not handed:
                raise RuntimeError(f"{model.name}: the program asked its model for the "
                                   f"base a second time; the benchmark hands it out once")
            return handed.pop()

    return Handed(model.name, model.defs, model.apply, model.num_classes,
                  model.input_shape, model.is_sequence), handed


# weak references to the leaves of the base last handed to the program
_handed_leaves: List[Any] = []


def build(config: Dict[str, Any], traffic: Dict[str, Any], fed: tr.Federation,
          weights: Any, seed: int, frozen: Any = None):
    """A ``Trainer`` for the cell, holding ``weights`` as its global model
    (the tree it trains) and ``frozen``, where given, as the base it
    freezes.  Keeps only weak references to ``frozen``, for ``release``."""
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
    unused = []
    if frozen is not None:
        _check_tree(f"{cfg.model} frozen base",
                    jax.eval_shape(model.init, jax.random.PRNGKey(0)), frozen)
        _handed_leaves[:] = [weakref.ref(a) for a in jax.tree_util.tree_leaves(frozen)]
        model, unused = _handing(model, frozen)
        del frozen
    x0, y0 = fed.data(0)
    fed_data = FederatedDataset(clients=LazyClients(fed), test=ClientData(x0[:1], y0[:1]),
                                num_classes=fed.num_classes)
    server = CohortServer(model, cfg, fed_data.test)
    trainer = Trainer(cfg, model, fed_data, server=server)
    if unused:
        raise ValueError(f"{cfg.model}: the configuration has a frozen tree, "
                         f"and the program froze none")
    _check_tree(f"{trainer.model.name} parameters",
                jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0)), weights)
    trainer.server.params = weights
    return trainer


def release() -> int:
    """Once the system is freed, drop what the program and JAX cache (the
    program's ``lru_cache``d round programs and the models, and a frozen
    base, that they close over; JAX's compiled programs), so that the
    reference holds the only base on the chip.  Leaves of the handed base
    that something of the program still holds are deleted from the
    device; returns how many were."""
    for name, module in list(sys.modules.items()):
        if name == "repro" or name.startswith("repro."):
            for fn in list(vars(module).values()):
                if isinstance(fn, functools._lru_cache_wrapper):
                    fn.cache_clear()
    jax.clear_caches()
    gc.collect()
    kept = [a for a in (r() for r in _handed_leaves) if a is not None]
    for a in kept:
        a.delete()
    _handed_leaves.clear()
    return len(kept)


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
