"""Aggregation stage (paper Fig. 3, server side).

FedAvg [McMahan et al., AISTATS'17]: sample-count-weighted average of client
updates applied to the global model.  The heavy inner loop — a weighted sum
over N client update vectors — has a Pallas TPU kernel
(``repro.kernels.fedavg_agg``); ``use_kernel`` switches it in, the pure-jnp
path is its oracle.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def fedavg_weights(num_samples: Sequence[int]) -> np.ndarray:
    w = np.asarray(num_samples, dtype=np.float64)
    return (w / w.sum()).astype(np.float32)


def weighted_train_loss(results: List[Dict]) -> float:
    """num_samples-weighted cohort loss — FedAvg semantics, shared by the
    local and remote runtimes (an unweighted mean over-counts tiny clients
    under unbalanced cohorts)."""
    counts = np.asarray([r.get("num_samples", 1) for r in results],
                        np.float64)
    losses = np.asarray([r["metrics"]["loss"] for r in results], np.float64)
    if counts.sum() <= 0:
        return float(np.mean(losses))
    return float(losses @ (counts / counts.sum()))


def weighted_average(updates: List[PyTree], weights: np.ndarray,
                     use_kernel: bool = False, topology: str = "flat",
                     fanout: int = 0) -> PyTree:
    """Weighted mean over a list of pytrees (equal structure).

    ``topology="hierarchical"`` routes the flattened matrix through the
    edge→region→global reduction tree
    (``kernels.fedavg_agg.fedavg_aggregate_tree``) with ``fanout``
    children per node — bit-equal to flat when ``fanout >= len(updates)``.
    """
    weights = jnp.asarray(weights, jnp.float32)

    if use_kernel or topology == "hierarchical":
        from jax.flatten_util import ravel_pytree
        from repro.kernels import ops as kops
        flats = [ravel_pytree(u)[0] for u in updates]
        unravel = ravel_pytree(updates[0])[1]
        stacked = jnp.stack(flats)               # (N, D)
        if topology == "hierarchical":
            from repro.kernels.fedavg_agg import fedavg_aggregate_tree
            return unravel(fedavg_aggregate_tree(
                stacked, weights, fanout=fanout, use_kernel=use_kernel))
        return unravel(kops.fedavg_aggregate(stacked, weights))

    def avg(*leaves):
        stacked = jnp.stack([l.astype(jnp.float32) for l in leaves])
        return jnp.einsum("n,n...->...", weights, stacked,
                          precision=jax.lax.Precision.HIGHEST)

    return jax.tree_util.tree_map(avg, *updates)


def staleness_weighted_delta(updates: List[PyTree],
                             num_samples: Sequence[int],
                             staleness: Sequence[float],
                             power: float = 0.5,
                             use_kernel: bool = False,
                             topology: str = "flat",
                             fanout: int = 0) -> PyTree:
    """FedBuff-style aggregate: sample-weighted mean with stale updates
    discounted by ``1/(1+s)^power`` (Nguyen et al., AISTATS'22).

    ``staleness[i]`` counts server aggregations between update i's dispatch
    and now (0 = trained on the current model).  The discount is a pure
    weight transform (``kernels.fedavg_agg.fold_staleness``), so the
    streaming Pallas kernel and the sharded psum path are reused unchanged.
    """
    from repro.kernels.fedavg_agg import fold_staleness
    w = np.asarray(fold_staleness(jnp.asarray(fedavg_weights(num_samples)),
                                  jnp.asarray(staleness, jnp.float32),
                                  power))
    return weighted_average(updates, w, use_kernel=use_kernel,
                            topology=topology, fanout=fanout)


def apply_delta(global_params: PyTree, delta: PyTree,
                server_lr: float = 1.0) -> PyTree:
    """Apply an aggregated update delta to the global params."""
    return jax.tree_util.tree_map(
        lambda p, d: (p.astype(jnp.float32) + server_lr * d).astype(p.dtype),
        global_params, delta)


def fedavg(global_params: PyTree, updates: List[PyTree],
           num_samples: Sequence[int], use_kernel: bool = False,
           server_lr: float = 1.0, topology: str = "flat",
           fanout: int = 0) -> PyTree:
    """Apply the weighted-average *update* (delta) to the global params."""
    delta = weighted_average(updates, fedavg_weights(num_samples), use_kernel,
                             topology=topology, fanout=fanout)
    return apply_delta(global_params, delta, server_lr)


AGGREGATORS = {"fedavg": fedavg}


def get_aggregator(name: str):
    if name not in AGGREGATORS:
        raise KeyError(f"unknown aggregator {name!r}")
    return AGGREGATORS[name]
