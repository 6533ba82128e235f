"""The control and the planted faults that the limits are set against.

The control is the plain reference put in the program's place at the
nearest precision below the configuration's: its clients train in
bfloat16 (``reference.run_reference(precision="bf16")``), the step that
would tempt a later change.  The faults are planted in the program itself:
a round that leaves the global weights unchanged; a FedAvg that leaves
out half of the cohort and takes the mean over the rest; FedAvg weights
that sum to 0.7, a server step off by 30%; error feedback whose stored
residuals are never added back.  Each has to come out as not correct.  The benchmark's own runs plant nothing.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import numpy as np

import reference as refm
import traffic as tr


def control_check(bench, workload: str, seed: int,
                  want: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The comparison's numbers for the control on one seed, with the
    per-leaf norms they come from; ``want`` is the float32 reference of
    the seed where the caller has it already."""
    wl = bench.workload(workload)
    config, ref = bench.config(wl["config"]), bench.reference(wl["config"])
    traffic = bench.traffic(wl["traffic"])
    rounds = int(bench.limits(workload)["rounds"])
    fed = tr.Federation(traffic, config, seed)
    if want is None:
        want = refm.run_reference(ref, config, fed, traffic, seed, rounds, "f32")
    got = refm.run_reference(ref, config, fed, traffic, seed, rounds, "bf16")
    return {"numbers": refm.compare(got, want), "leaves": refm.leaf_table(got, want)}


@contextlib.contextmanager
def unchanged_state():
    """Every round hands back the global weights it was given."""
    import jax
    import jax.numpy as jnp
    from repro.core.rounds import Trainer

    orig = Trainer.run_round

    def run_round(self, round_id):
        before = jax.tree_util.tree_map(jnp.copy, self.server.params)
        out = orig(self, round_id)
        self.server.params = before
        return out

    Trainer.run_round = run_round
    try:
        yield
    finally:
        Trainer.run_round = orig


@contextlib.contextmanager
def half_cohort():
    """FedAvg weighs only the first half of the cohort, renormalised."""
    from repro.core import aggregation

    orig = aggregation.fedavg_weights

    def weights(num_samples):
        w = np.asarray(orig(num_samples), np.float64)
        w[(len(w) + 1) // 2:] = 0.0
        return (w / w.sum()).astype(np.float32)

    aggregation.fedavg_weights = weights
    try:
        yield
    finally:
        aggregation.fedavg_weights = orig


@contextlib.contextmanager
def scaled_weights():
    """FedAvg's weights sum to 0.7 instead of 1."""
    from repro.core import aggregation

    orig = aggregation.fedavg_weights

    def weights(num_samples):
        return (np.asarray(orig(num_samples), np.float32) * np.float32(0.7))

    aggregation.fedavg_weights = weights
    try:
        yield
    finally:
        aggregation.fedavg_weights = orig


@contextlib.contextmanager
def no_residual():
    """Every round finds the error-feedback store all zero: the residuals
    that compression left behind are never added back."""
    import jax.numpy as jnp
    from repro.core.batched import BatchedExecutor

    orig = BatchedExecutor.run_round_fused

    def run_round_fused(self, *args, **kwargs):
        if self._ef is not None and self._ef.leaves:
            self._ef.leaves = [jnp.zeros_like(a) for a in self._ef.leaves]
        return orig(self, *args, **kwargs)

    BatchedExecutor.run_round_fused = run_round_fused
    try:
        yield
    finally:
        BatchedExecutor.run_round_fused = orig


FAULTS = {"unchanged_state": unchanged_state, "half_cohort": half_cohort,
          "scaled_weights": scaled_weights, "no_residual": no_residual}
