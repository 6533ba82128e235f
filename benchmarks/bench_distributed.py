"""Mesh-sharded batched execution (``resources.distributed = "data"``).

Measures, in process, per shard count K (the powers of two up to the
number of devices present):

* round wall time with the stacked client dimension sharded K ways
  (params replicated, client data / local states sharded) vs the
  unsharded batched baseline;
* the per-shard client count (cohort bucket / K) — the memory the mesh
  saves per device;
* sharded FedAvg aggregation (per-shard partials + psum epilogue) time.

It starts no child process: a chip belongs to one process, so a child
of a process that already holds the devices could not reach them.  On a
single-device host only K = 1 runs; the multi-device mechanism is
rehearsed on forced host devices by ``tests/test_distributed_batched.py``.

    PYTHONPATH=src python -m benchmarks.bench_distributed
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, Optional

N_CLIENTS = 64


def _make_trainer(n: int, devices=None):
    import jax

    from repro.core.batched import BatchedExecutor
    from repro.core.config import Config
    from repro.core.rounds import Trainer
    from repro.core.server import Server
    from repro.data.fed_data import build_federated_data
    from repro.models.registry import get_model

    cfg = Config.make({
        "model": "linear",
        "data": {"dataset": "synthetic", "num_clients": n, "batch_size": 32},
        "server": {"rounds": 2, "clients_per_round": n, "test_every": 0},
        "client": {"local_epochs": 2, "lr": 0.1},
        "resources": {"execution": "batched",
                      "distributed": "data" if devices else "none"},
        "tracking": {"enabled": False},
    })
    model = get_model(cfg.model)
    fed = build_federated_data(cfg.data)
    trainer = Trainer(cfg, model, fed, server=Server(model, cfg, fed.test))
    if devices:
        trainer.engine = BatchedExecutor(model, max_clients=n, distributed="data",
                                         devices=devices)
    trainer.server.params = model.init(jax.random.PRNGKey(cfg.seed))
    return trainer


def _shard_counts():
    import jax
    return tuple(1 << i for i in range(len(jax.devices()).bit_length()))


def collect(ks: Optional[Iterable[int]] = None,
            n: int = N_CLIENTS) -> Dict[str, Dict]:
    import jax
    import numpy as np

    from repro.core.batched import bucket_pow2, build_client_mesh
    from repro.kernels.fedavg_agg import fedavg_aggregate_sharded

    out: Dict[str, Dict] = {"round_s": {}, "clients_per_shard": {},
                            "agg_sharded_s": {}}
    trainer = _make_trainer(n)
    trainer.run_round(0)                      # warm-up (compile)
    t0 = time.perf_counter()
    trainer.run_round(1)
    out["round_s"]["batched"] = time.perf_counter() - t0
    out["clients_per_shard"]["batched"] = bucket_pow2(n)

    rng = np.random.RandomState(0)
    u = rng.randn(n, 50_000).astype(np.float32)
    w = (np.ones(n) / n).astype(np.float32)
    for k in ks or _shard_counts():
        if k > len(jax.devices()):
            continue
        trainer = _make_trainer(n, devices=jax.devices()[:k])
        trainer.run_round(0)
        t0 = time.perf_counter()
        trainer.run_round(1)
        out["round_s"][str(k)] = time.perf_counter() - t0
        out["clients_per_shard"][str(k)] = max(bucket_pow2(n), k) // k

        mesh = build_client_mesh(jax.devices()[:k])
        agg = fedavg_aggregate_sharded(u, w, mesh)
        jax.block_until_ready(agg)
        t0 = time.perf_counter()
        jax.block_until_ready(fedavg_aggregate_sharded(u, w, mesh))
        out["agg_sharded_s"][str(k)] = time.perf_counter() - t0
    return out


def main() -> None:
    from benchmarks.common import emit

    data = collect()
    rows = []
    base = data["round_s"]["batched"]
    rows.append((f"dist_roundtime_s_batched_N{N_CLIENTS}", base,
                 f"{data['clients_per_shard']['batched']} clients/device"))
    for key in data["agg_sharded_s"]:
        rows.append((f"dist_roundtime_s_mesh{key}_N{N_CLIENTS}",
                     data["round_s"][key],
                     f"{data['clients_per_shard'][key]} clients/shard"))
        rows.append((f"dist_agg_psum_s_mesh{key}", data["agg_sharded_s"][key],
                     "per-shard partials + psum"))
    emit(rows)


if __name__ == "__main__":
    main()
