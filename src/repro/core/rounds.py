"""Round orchestration: the runtime behind ``easyfl.run()``.

Combines every platform module per the FL life cycle (§III):
  simulation manager (heterogeneity) + data manager + server/client stages +
  distribution manager (GreedyAda, §VI) + tracking manager (§V-C).

Timing model: each client's *measured* local-training time is recorded; the
system-heterogeneity simulator scales it by the client's device-class speed
ratio (virtual clock — DESIGN.md §2).  The round's virtual duration is the
makespan of the device groups, exactly Eq. 1:

    T_round = max_g  sum_{c in g} simulated_time(c)

GreedyAda is fed the *simulated* times (that is what a real heterogeneous
deployment would measure), so the scheduler optimizes against stragglers.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.aggregation import weighted_train_loss
from repro.core.batched import BatchedExecutor
from repro.core.client import Client
from repro.core.config import Config, validate_config
from repro.core.server import Server
from repro.core import compression as comp
from repro.data.fed_data import FederatedDataset
from repro.sched.greedyada import (
    ClientProfile, GreedyAda, one_per_device, random_allocation,
    slowest_allocation,
)
from repro.simulation.heterogeneity import (
    FaultInjector, FaultPlan, SystemHeterogeneity,
)
from repro.tracking import Tracker


def _poison_update(update):
    """Corrupt an uploaded update with NaNs (``faults.nan_update_prob``).

    Applied *after* the compression stage — the model is a corrupted wire
    payload, so the client's error-feedback residual stays clean.  For
    ``CompressedTensor`` leaves the structure (and therefore the byte
    accounting) is preserved: float payloads are poisoned directly, int8
    payloads through their dequantization scale."""
    nan = np.float32("nan")

    def one(x):
        if isinstance(x, comp.CompressedTensor):
            if x.kind == "int8":
                return comp.CompressedTensor(x.kind, x.data, x.scale * nan,
                                             x.nnz)
            return comp.CompressedTensor(
                x.kind, np.asarray(x.data, np.float32) * nan, x.scale, x.nnz)
        return np.asarray(x, np.float32) * nan

    return jax.tree_util.tree_map(
        one, update, is_leaf=lambda x: isinstance(x, comp.CompressedTensor))


def dense_update_bytes(params) -> int:
    """Wire size of one dense (uncompressed) update of ``params``' shape.

    Per-leaf ``dtype.itemsize`` — NOT a hardcoded 4 bytes/element — so
    bf16/f16/mixed-dtype trees and LoRA adapter trees report what would
    actually cross the wire."""
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(params))


def update_is_valid(update, max_norm: float = 0.0) -> bool:
    """Host-side NaN/Inf + norm-outlier guard for a gathered update.

    The batched fast path runs the identical checks on-device on the
    stacked update matrix (``BatchedExecutor.aggregate_stacked``); this is
    the sequential/async/fallback twin.  ``max_norm`` bounds the update's
    global L2 norm (0 disables the bound)."""
    dense = comp.decompress(update)
    sq = 0.0
    for leaf in jax.tree_util.tree_leaves(dense):
        a = np.asarray(leaf, np.float32)
        if not np.isfinite(a).all():
            return False
        if max_norm > 0:
            sq += float(np.sum(np.square(a.astype(np.float64))))
    return not (max_norm > 0 and sq > float(max_norm) ** 2)


class Trainer:
    def __init__(self, config: Config, model, fed_data: FederatedDataset,
                 tracker: Optional[Tracker] = None,
                 server: Optional[Server] = None,
                 client_cls=Client):
        self.cfg = config
        # whole-tree validation (repro.core.config.validate_config) first —
        # the client.finetune fields drive the model wrapping below
        validate_config(config)
        if config.client.finetune == "lora":
            # Freeze the base model and train low-rank adapters only: the
            # wrapper *is* an FLModel whose param tree holds just the A/B
            # factors, so every engine/aggregation/compression/checkpoint
            # stage below operates on adapters with zero changes (and
            # comm_up_bytes automatically counts only adapter payload).
            # The base is initialized once from cfg.seed and closed over —
            # replicated per program, never per client.
            from repro.models.lora import lora_wrap
            wrapped = lora_wrap(
                model, model.init(jax.random.PRNGKey(config.seed)),
                config.client.lora_rank, config.client.lora_alpha,
                config.client.lora_targets)
            if not wrapped.defs:
                raise ValueError(
                    f"client.finetune='lora' with lora_targets="
                    f"{config.client.lora_targets!r} matched no eligible "
                    f"matrix leaves of model {model.name!r} (eligible: "
                    f">= 2 dims beyond a stacked 'layers' axis) — nothing "
                    f"to train")
            model = wrapped
            if server is not None:
                # a caller-built server was constructed around the base
                # model; evaluation/aggregation must see the adapter model
                server.model = model
        self.model = model
        self.fed_data = fed_data
        self.tracker = tracker or Tracker(
            config.tracking.backend, config.tracking.out_dir,
            client_history_rounds=config.tracking.client_history_rounds)
        self.server = server or Server(model, config, fed_data.test)
        self.client_cls = client_cls
        self.clients: Dict[str, Client] = {}
        res = config.resources
        self.faults = FaultInjector(config.faults)
        if config.faults.active and \
                config.faults.min_clients_per_round > \
                config.server.clients_per_round:
            raise ValueError(
                f"faults.min_clients_per_round="
                f"{config.faults.min_clients_per_round} can never be met: "
                f"only server.clients_per_round="
                f"{config.server.clients_per_round} clients are selected "
                f"per round")
        # async dispatch waves run through the batched executor too
        # rows the engine keeps on the device: every client a synchronous
        # run can touch (rounds x cohort), or the clients in flight under
        # async waves (finished ones spill to the host)
        cohort = config.server.clients_per_round
        max_clients = (config.server.rounds * cohort
                       if res.execution == "batched"
                       else max(res.max_concurrency, cohort))
        max_clients = max(1, min(max_clients, len(fed_data.client_ids)))
        self.engine = (BatchedExecutor(model, distributed=res.distributed,
                                       max_clients=max_clients)
                       if res.execution in ("batched", "async") else None)
        self.het = SystemHeterogeneity(config.system_heterogeneity)
        self.scheduler = GreedyAda(
            num_devices=max(1, config.resources.num_devices),
            default_time=config.resources.default_client_time,
            momentum=config.resources.momentum)
        self.history: List[Dict[str, float]] = []
        # error-feedback residuals loaded from a checkpoint, applied
        # lazily when the owning client is materialized
        self._pending_residuals: Dict[str, Any] = {}
        # one loud warning per trainer when resources.round_fusion="auto"
        # cannot fuse a synchronous batched round (docs/perf.md)
        self._fusion_warned = False

    # ------------------------------------------------------------------
    # Materialized-Client cache bound: with virtual million-client
    # populations the touched-client set grows every round, so Client
    # objects (which pin their ClientData shard on the host) are evicted
    # FIFO past this bound — except clients carrying sequential-path
    # error-feedback residuals, which are state, not recomputable.
    CLIENT_CACHE_MAX = 4096

    def client(self, cid: str) -> Client:
        if cid not in self.clients:
            if len(self.clients) >= self.CLIENT_CACHE_MAX:
                for old in [c for c, cl in self.clients.items()
                            if cl._residual is None][
                                : len(self.clients) - self.CLIENT_CACHE_MAX + 1]:
                    del self.clients[old]
            ccfg = self.cfg.client
            overrides = self.het.hyperparam_overrides(cid)
            if overrides:
                # per-client optimizer heterogeneity, sampled
                # deterministically from system_heterogeneity.
                # hyperparam_choices — every sampled field is vectorized
                # by the batched/async cohort program
                ccfg = dataclasses.replace(ccfg, **overrides)
            self.clients[cid] = self.client_cls(
                cid, self.model, self.fed_data.clients[cid],
                ccfg, batch_size=self.cfg.data.batch_size)
            if cid in self._pending_residuals:
                # restore checkpointed error-feedback state (sequential
                # compression path; the batched engines keep theirs in the
                # executor's device-resident store)
                self.clients[cid]._residual = jax.tree_util.tree_map(
                    jnp.asarray, self._pending_residuals.pop(cid))
        return self.clients[cid]

    def _allocate(self, selected: List[str], round_id: int) -> List[List[str]]:
        name = self.cfg.resources.allocation
        M = max(1, self.cfg.resources.num_devices)
        if name == "greedy_ada":
            return self.scheduler.allocate(selected)
        if name == "random":
            return random_allocation(selected, M, seed=round_id)
        if name == "slowest":
            est = {c: self.scheduler._estimate(c) for c in selected}
            return slowest_allocation(selected, M, est)
        if name == "one_per_device":
            return one_per_device(selected)
        raise ValueError(f"unknown allocation {name!r}")

    # ------------------------------------------------------------------
    # fault injection (cfg.faults — docs/faults.md)
    # ------------------------------------------------------------------
    def _plan_cohort(self, selected: List[str], round_id: int):
        """Sample each selected client's :class:`FaultPlan`; when fewer
        than ``faults.min_clients_per_round`` clients would survive the
        pre-known failures (dropout/crash), re-select the cohort (bounded
        attempts, then a loud ``ValueError``) instead of silently
        aggregating a tiny one.  Deadline misses and guard rejections are
        only known post-hoc and do not re-trigger selection."""
        f = self.cfg.faults
        floor = min(f.min_clients_per_round, len(selected))
        attempts = 0
        reselections = 0
        while True:
            plans = {c: self.faults.plan(c, round_id) for c in selected}
            alive = sum(1 for p in plans.values() if not p.fails)
            if alive >= floor:
                return selected, plans, reselections
            attempts += 1
            if attempts > 20:
                raise ValueError(
                    f"faults.min_clients_per_round="
                    f"{f.min_clients_per_round}: could not assemble a "
                    f"cohort with >= {floor} surviving clients after "
                    f"{attempts} selection attempts in round {round_id} "
                    f"(last draw: {alive}/{len(selected)} survivors); "
                    f"lower dropout/crash probabilities or the floor")
            reselections += 1
            selected = self.server.selection(self.fed_data.client_ids,
                                             round_id)

    def _effective_time(self, cid: str, base: float,
                        plan: Optional[FaultPlan]) -> float:
        """Virtual response time under a fault plan: stragglers scale the
        training time before the heterogeneity simulation, a crash elapses
        only ``crash_fraction`` of the round, and a dropout never responds
        (0 contribution to the makespan)."""
        if plan is None:
            return self.het.simulate_time(cid, base)
        if plan.dropout:
            return 0.0
        f = self.cfg.faults
        t = base * (f.straggler_slowdown if plan.straggler else 1.0)
        t = self.het.simulate_time(cid, t)
        if plan.crash:
            t *= plan.crash_fraction
        return t

    # ------------------------------------------------------------------
    def _batched_cohort(self, selected: List[str], payload: Dict[str, Any]):
        """The selected clients (each built, and its data drawn, on first
        use) and the global params they all train from.  The pre-train
        stages run once, through the first client; per-client pre-train
        or ``train`` overrides cannot be vectorized and raise."""
        clients = [self.client(c) for c in selected]
        for stage in ("download", "decompression", "train"):
            impls = {getattr(type(c), stage) for c in clients}
            if len(impls) > 1 or (stage == "train"
                                  and impls != {Client.train}):
                raise ValueError(
                    f"batched execution cannot vectorize per-client "
                    f"{stage!r} overrides ({[type(c).__name__ for c in clients]}); "
                    f"use resources.execution='sequential'")
        return clients, clients[0].decompression(clients[0].download(payload))

    def _run_batched(self, selected: List[str], payload: Dict[str, Any],  # flcheck: hot
                     round_id: int,
                     plans: Optional[Dict[str, FaultPlan]] = None,
                     counts: Optional[Dict[str, int]] = None,
                     cohort=None):
        """Train the whole cohort in one compiled program, then run each
        client's post-train stages (compression/encryption/upload) so
        strategy overrides like STC keep working.

        The pre-train stages run ONCE for the cohort (all clients receive
        the same payload), through the first client's download/decompression
        so uniform stage overrides are honored; heterogeneous pre-train or
        ``train`` overrides cannot be vectorized and raise instead of
        silently diverging.

        ``cohort`` is :meth:`_batched_cohort` of ``selected`` where the
        caller has built it already (inside its ``fl.cohort`` span).

        Returns ``(results, aggregated, finish)``; ``finish`` is ``None``
        except on fused rounds, where the caller invokes it (inside its
        ``fl.finalize`` span) to fill in ``metrics`` / ``payload_bytes``;
        on a deferred fused round (``tracking.round_sync=False``) it first
        runs the round's single batched metric fetch.
        With ``resources.round_fusion="auto"`` (default), an eligible
        synchronous round additionally fuses compression, fault
        weighting, aggregation AND the server apply into ONE dispatch
        (``BatchedExecutor.run_round_fused``); ineligible rounds warn
        once and fall back to the staged fast path below.

        With default post-train stages
        and plain FedAvg, synchronous batched rounds take the **no-gather
        fast path**: the stacked updates are — for the built-in
        ``client.compression = "stc"/"int8"`` — compressed *inside* the
        stacked pipeline (batched Pallas kernels + the executor's
        error-feedback residual store, ``BatchedExecutor.compress_stacked``)
        and aggregated in place (``aggregate_stacked``: per-shard partial
        weighted sums + psum on the client mesh under
        ``resources.distributed="data"``, a stacked einsum / streaming
        kernel on one device), so ``aggregated=True`` and the per-client
        results carry metrics and byte accounting (STC sizes from the
        in-program per-client nnz) but no ``"update"`` — client updates
        never gather to the host.

        Anything else falls back — loudly documented here — to the
        gathering path (per-client update extraction + per-client Python
        post-train stages): per-client *overrides* of the compression /
        encryption / upload stages (e.g. ``STCClient``, whose stage
        override the engine cannot see inside), a non-FedAvg aggregator, a
        ``Server.aggregation`` override, or an unknown ``compression``
        name.  Asynchronous dispatch waves also use the in-program
        compression (residuals keyed by client id across waves) but return
        their per-client *sent* updates un-aggregated (``aggregated=False``)
        — the event loop buffers them for staleness-weighted FedBuff
        aggregation."""
        clients, global_params = (
            cohort if cohort is not None
            else self._batched_cohort(selected, payload))

        method = self.cfg.client.compression
        default_post = all(
            type(c).compression is Client.compression
            and type(c).encryption is Client.encryption
            and type(c).upload is Client.upload for c in clients)
        is_async = self.cfg.resources.execution == "async"
        # Synchronous rounds with a non-FedAvg aggregator or a
        # Server.aggregation override take the gathering fallback even for
        # built-in compression (the override may inspect the
        # CompressedTensor leaves the per-client stage produces); async
        # waves always compress in-program — the event loop has already
        # validated the server speaks FedBuff (buffered_apply/fedavg).
        inprogram = is_async and default_post and method in ("stc", "int8")
        fuse_agg = (
            not is_async
            and default_post
            and method in ("none", "stc", "int8")
            and self.cfg.server.aggregation == "fedavg"
            and type(self.server).aggregation is Server.aggregation)
        # Whole-round fusion (resources.round_fusion="auto"): the fast
        # path's remaining eligibility is an un-overridden apply_delta
        # (the apply runs in-program) and no round_deadline (deadline
        # masking needs the round's own measured wall time, which does not
        # exist until the single dispatch completes).
        fuse_round = (
            fuse_agg
            and self.cfg.resources.round_fusion == "auto"
            and self.cfg.resources.round_deadline == 0
            and type(self.server).apply_delta is Server.apply_delta)
        if not is_async and not fuse_round \
                and self.cfg.resources.round_fusion == "auto" \
                and not self._fusion_warned:
            reasons = []
            if not default_post:
                reasons.append("per-client compression/encryption/upload "
                               "stage overrides")
            if method not in ("none", "stc", "int8"):
                reasons.append(f"client.compression={method!r}")
            if self.cfg.server.aggregation != "fedavg":
                reasons.append(f"server.aggregation="
                               f"{self.cfg.server.aggregation!r} (non-FedAvg)")
            if type(self.server).aggregation is not Server.aggregation:
                reasons.append("a Server.aggregation override")
            if type(self.server).apply_delta is not Server.apply_delta:
                reasons.append("a Server.apply_delta override")
            if self.cfg.resources.round_deadline > 0:
                reasons.append("resources.round_deadline > 0 (deadline "
                               "masking needs the measured round time)")
            self._fusion_warned = True
            warnings.warn(
                "resources.round_fusion='auto' cannot fuse this round into "
                "one program (" + "; ".join(reasons) + "); falling back to "
                "the staged batched path — set round_fusion='off' to "
                "silence (docs/perf.md)", stacklevel=3)
        if fuse_round:
            # ---- the fused fast path: ONE dispatch for the whole round
            # (train + compress/EF + fault mask/guard + FedAvg + apply),
            # one batched device->host fetch for metrics/accounting ----
            labels: Dict[str, str] = {}
            mask = None
            nan_rows: List[int] = []
            if plans is not None:
                # dropout/crash are known before the round runs, so the
                # survival mask is an input of the single dispatch (the
                # on-device guard still catches NaN/norm outliers)
                mask = np.ones((len(clients),), np.float32)
                for i, client in enumerate(clients):
                    p = plans[client.client_id]
                    if p.dropout:
                        mask[i], labels[client.client_id] = 0.0, "dropped"
                    elif p.crash:
                        mask[i], labels[client.client_id] = 0.0, "crashed"
                nan_rows = [i for i, c in enumerate(clients)
                            if plans[c.client_id].nan_update]
            st, new_params, fetch = self.engine.run_round_fused(
                clients, global_params, round_id,
                method=method, stc_sparsity=self.cfg.client.stc_sparsity,
                use_kernel=self.cfg.resources.aggregation_kernel,
                topology=self.cfg.resources.aggregation_topology,
                fanout=self.cfg.resources.aggregation_fanout,
                use_faults=plans is not None, mask=mask, nan_rows=nan_rows,
                max_update_norm=(self.cfg.faults.max_update_norm
                                 if plans is not None else 0.0),
                server_lr=self.cfg.server.server_lr,
                sync=self.cfg.tracking.round_sync)
            self.server.params = new_params

            total_steps = max(int(st["n_steps"][: len(clients)].sum()), 1)
            steps_f = st["n_steps"].astype(np.float64).tolist()
            results = [
                {"client_id": c.client_id, "num_samples": len(c.data),
                 "train_time": st["wall"] * steps_f[i] / total_steps}
                for i, c in enumerate(clients)]

            def complete():
                """Metric/accounting assembly from the round's single
                batched fetch (already host-resident in ``st``)."""
                loss, acc = st["loss"].tolist(), st["acc"].tolist()
                for i, res in enumerate(results):
                    res["metrics"] = {"loss": loss[i], "accuracy": acc[i],
                                      "batches": steps_f[i]}
                if method != "none":
                    payloads = self.engine.per_client_payload_bytes(st)
                else:
                    # dense update wire size from each leaf's real dtype
                    payloads = ([dense_update_bytes(global_params)]
                                * len(clients))
                for res, pb in zip(results, payloads):
                    res["payload_bytes"] = pb
                if plans is not None:
                    ok = st["guard_ok"]
                    for i, res in enumerate(results):
                        lab = labels.get(res["client_id"])
                        if lab is None and not ok[i]:
                            lab = "rejected"
                            counts["rejected"] += 1
                        if lab is not None:
                            res["_fault"] = lab

            if fetch is None:
                return results, True, complete

            def finish():
                fetch()
                complete()
            return results, True, finish
        if fuse_agg:
            st = self.engine.run_cohort_stacked(clients, global_params,
                                                round_id)
            if method != "none":
                st = self.engine.compress_stacked(
                    st, clients, method, self.cfg.client.stc_sparsity)
            # Fault degradation on the fast path (cfg.faults): failed /
            # deadline-exceeded clients are zero-weighted out of the
            # FedAvg weight vector and NaN-injected uploads are poisoned
            # post-compression (the error-feedback residuals stay clean)
            # so the on-device guard in aggregate_stacked rejects them.
            # The cohort still trains at full bucketed width — no shape
            # change, no retrace — and with faults inactive every branch
            # below is skipped, leaving the PR 1-5 pipeline byte-identical.
            labels: Dict[str, str] = {}
            mask = None
            if plans is not None:
                mask = np.ones((len(clients),), np.float32)
                total_steps = max(int(st["n_steps"][: len(clients)].sum()),
                                  1)
                steps_f = np.asarray(st["n_steps"], dtype=np.float64)
                deadline = self.cfg.resources.round_deadline
                for i, client in enumerate(clients):
                    p = plans[client.client_id]
                    base = st["wall"] * steps_f[i] / total_steps
                    eff = self._effective_time(client.client_id, base, p)
                    if p.dropout:
                        mask[i], labels[client.client_id] = 0.0, "dropped"
                    elif p.crash:
                        mask[i], labels[client.client_id] = 0.0, "crashed"
                    elif deadline > 0 and eff > deadline:
                        mask[i], labels[client.client_id] = 0.0, "deadline"
                        counts["deadline_missed"] += 1
                nan_rows = np.asarray(
                    [i for i, c in enumerate(clients)
                     if plans[c.client_id].nan_update], np.int32)
                if nan_rows.size:
                    # a select, not a scatter: see tiered_store.set_rows
                    def poison(a):
                        hit = np.zeros((a.shape[0],) + (1,) * (a.ndim - 1),
                                       bool)
                        hit[nan_rows] = True
                        return jnp.where(hit, jnp.nan, a)

                    st["updates"] = jax.tree_util.tree_map(poison,
                                                           st["updates"])
            delta = self.engine.aggregate_stacked(
                st, use_kernel=self.cfg.resources.aggregation_kernel,
                mask=mask, guard=plans is not None,
                max_update_norm=(self.cfg.faults.max_update_norm
                                 if plans is not None else 0.0),
                topology=self.cfg.resources.aggregation_topology,
                fanout=self.cfg.resources.aggregation_fanout)
            self.server.apply_delta(delta)
            results = self.engine.per_client_results(clients, st,
                                                     include_update=False)
            if method != "none":
                payloads = self.engine.per_client_payload_bytes(st)
            else:
                # dense update wire size, identical across the cohort
                payloads = ([dense_update_bytes(global_params)]
                            * len(clients))
            for client, res, pb in zip(clients, results, payloads):
                res["client_id"] = client.client_id
                res["payload_bytes"] = pb
            if plans is not None:
                # one small host sync (N bools) for rejection accounting —
                # only when faults are active
                ok = np.asarray(jax.device_get(st["guard_ok"]))  # flcheck: ignore[FLC101]  -- N bools, faults only
                for i, res in enumerate(results):
                    lab = labels.get(res["client_id"])
                    if lab is None and not ok[i]:
                        lab = "rejected"
                        counts["rejected"] += 1
                    if lab is not None:
                        res["_fault"] = lab
            return results, True, None

        if inprogram:
            # async wave: compress in-program, hand back per-client sent
            # (dense-decoded) updates for the FedBuff buffer
            st = self.engine.run_cohort_stacked(clients, global_params,
                                                round_id)
            st = self.engine.compress_stacked(
                st, clients, method, self.cfg.client.stc_sparsity)
            results = self.engine.per_client_results(clients, st)
            payloads = self.engine.per_client_payload_bytes(st)
            for client, res, pb in zip(clients, results, payloads):
                res["client_id"] = client.client_id
                res["payload_bytes"] = pb
            return results, False, None

        raw = self.engine.run_cohort(clients, global_params, round_id)
        results = []
        for client, res in zip(clients, raw):
            p = plans.get(client.client_id) if plans is not None else None
            if p is not None and p.fails:
                # the update never arrives; skip the post-train stages so
                # the client's error-feedback residual stays untouched
                # (the whole cohort still trained at full bucketed width —
                # no retrace).  run_round zero-weights via the label.
                res.pop("update", None)
                res["client_id"] = client.client_id
                res["_fault"] = "dropped" if p.dropout else "crashed"
                results.append(res)
                continue
            res = client.compression(res)
            res = client.encryption(res)
            res["client_id"] = client.client_id
            res = client.upload(res)
            if p is not None and p.nan_update:
                res["update"] = _poison_update(res["update"])
            results.append(res)
        return results, False, None

    # ------------------------------------------------------------------
    def run_round(self, round_id: int) -> Dict[str, float]:  # flcheck: hot
        """Dispatch round ``round_id`` and finalize its metrics.

        The round is internally split into a dispatch phase and a
        finalize phase (:meth:`_dispatch_round`) so the ``_run`` loop can
        — under ``tracking.round_sync=False`` — overlap round R's metric
        fetch with round R+1's dispatch; calling this method runs both
        back to back (the default, exact-clock behavior)."""
        with jax.profiler.StepTraceAnnotation("fl.round", step_num=round_id,
                                              round=round_id):
            return self._dispatch_round(round_id)()

    def _dispatch_round(self, round_id: int  # flcheck: hot
                        ) -> Callable[[], Dict[str, float]]:
        if self.cfg.resources.execution == "async":
            raise ValueError(
                'resources.execution="async" replaces the synchronous round '
                "loop with an event loop; call Trainer.run()")
        with jax.profiler.TraceAnnotation("fl.cohort", round=round_id):
            server = self.server
            f = self.cfg.faults
            deadline = self.cfg.resources.round_deadline
            selected = server.selection(self.fed_data.client_ids, round_id)
            plans = counts = None
            # a response deadline alone (faults off) still needs the
            # degradation path: plans are all NO_FAULT, only misses zero-weight
            if f.active or deadline > 0:
                selected, plans, reselections = self._plan_cohort(selected,
                                                                  round_id)
                counts = {"deadline_missed": 0, "rejected": 0,
                          "reselections": reselections,
                          "dropped": sum(p.dropout for p in plans.values()),
                          "crashed": sum(p.crash for p in plans.values()),
                          "straggled": sum(p.straggler
                                           for p in plans.values())}
            payload = server.distribution(selected)
            groups = self._allocate(selected, round_id)
            cohort = (self._batched_cohort(selected, payload)
                      if self.engine is not None else None)

        results, sim_times, wall_times = [], {}, {}
        aggregated, finish = False, None
        t_wall0 = time.perf_counter()
        down_bytes = payload.get("payload_bytes", 0) * len(selected)
        if self.engine is not None:
            results, aggregated, finish = self._run_batched(
                selected, payload, round_id, plans=plans, counts=counts,
                cohort=cohort)
            for res in results:
                cid = res["client_id"]
                wall_times[cid] = res["train_time"]
                sim_times[cid] = self._effective_time(
                    cid, res["train_time"],
                    plans[cid] if plans is not None else None)
        else:
            for group in groups:
                for cid in group:
                    p = plans[cid] if plans is not None else None
                    if p is not None and p.dropout:
                        # never responds; never even starts training
                        wall_times[cid] = sim_times[cid] = 0.0
                        continue
                    if p is not None and p.crash:
                        # dies mid-training: the update (and the
                        # post-train stages — EF residuals stay clean)
                        # never happens, but partial virtual time elapses
                        c = self.client(cid)
                        res = c.train(c.decompression(c.download(payload)),
                                      round_id)
                        res.pop("update")
                        res["client_id"] = cid
                        res["_fault"] = "crashed"
                    else:
                        res = self.client(cid).run_round(payload, round_id)
                        if p is not None and p.nan_update:
                            res["update"] = _poison_update(res["update"])
                    results.append(res)
                    wall_times[cid] = res["train_time"]
                    sim_times[cid] = self._effective_time(
                        cid, res["train_time"], p)
            # canonical selection order, not scheduler-group order: the
            # groups follow *measured* times, so without this the FedAvg
            # summation order (and the params, by one float ulp per round)
            # would vary run to run and break bit-identical checkpoint
            # resume (the batched path is already in selection order)
            order = {cid: i for i, cid in enumerate(selected)}
            results.sort(key=lambda r: order[r["client_id"]])
        if plans is not None and not aggregated:
            # graceful degradation for the gathered paths (the batched
            # fast path already zero-weighted on device): deadline misses
            # and guard rejections are only known post-hoc
            for res in results:
                cid = res["client_id"]
                if res.get("_fault") is not None:
                    continue
                if deadline > 0 and sim_times[cid] > deadline:
                    res["_fault"] = "deadline"
                    counts["deadline_missed"] += 1
                elif not update_is_valid(res["update"], f.max_update_norm):
                    res["_fault"] = "rejected"
                    counts["rejected"] += 1
        survivors = [r for r in results if r.get("_fault") is None]

        # Eq. 1 makespan under the virtual clock (the server stops
        # waiting at the deadline, so per-client contributions cap there)
        capped = (sim_times if plans is None or deadline <= 0 else
                  {c: min(t, deadline) for c, t in sim_times.items()})
        round_virtual = max(
            (sum(capped[c] for c in g) for g in groups if g), default=0.0)
        if plans is None:
            self.scheduler.update(sim_times)
        else:
            # a dropped client's 0.0 is no observation of its speed
            self.scheduler.update({c: t for c, t in sim_times.items()
                                   if not plans[c].dropout})
        if not aggregated and (plans is None or survivors):
            server.aggregation(survivors if plans is not None else results)
        wall = time.perf_counter() - t_wall0
        # the params this round produced: a deferred finalize must
        # evaluate these even after round R+1 has replaced server.params
        params_r = server.params

        def finalize() -> Dict[str, float]:
            with jax.profiler.TraceAnnotation("fl.finalize", round=round_id):
                if finish is not None:
                    finish()   # fused: accounting (deferred: its fetch first)
                survivors = [r for r in results if r.get("_fault") is None]
                # one batched host sync for the whole cohort's wire accounting
                # (compression.payload_bytes_many), instead of per-leaf
                # blocking reads per client; crashed/dropped/deadline-missed
                # uploads never reached the server, so their bytes don't count
                arrived = (results if plans is None else
                           [r for r in results
                            if r.get("_fault") in (None, "rejected")])
                up_bytes = sum(r["payload_bytes"] for r in arrived
                               if "payload_bytes" in r)
                missing = [r for r in arrived if "payload_bytes" not in r]
                if missing:
                    up_bytes += sum(comp.payload_bytes_many(
                        [r["update"] for r in missing]))

                train_loss = weighted_train_loss(
                    survivors if plans is not None else results) \
                    if plans is None or survivors else float("nan")
                metrics = {
                    "round_time": round_virtual,
                    "wall_time": wall,
                    "clients": len(selected),
                    "comm_down_bytes": down_bytes,
                    "comm_up_bytes": up_bytes,
                    "train_loss": train_loss,
                }
                if plans is not None:
                    metrics.update(
                        survivors=len(survivors),
                        survivor_fraction=(len(survivors)
                                           / max(len(selected), 1)),
                        **counts)
                if self.cfg.server.test_every and \
                   (round_id + 1) % self.cfg.server.test_every == 0:
                    saved = server.params
                    server.params = params_r
                    try:
                        metrics.update(server.test())
                    finally:
                        server.params = saved

                if self.cfg.tracking.enabled:
                    self.tracker.track_round(self.cfg.task_id, round_id,
                                             **metrics)
                    for r in results:
                        extra = ({} if r.get("_fault") is None
                                 else {"fault": r["_fault"]})
                        self.tracker.track_client(
                            self.cfg.task_id, round_id, r["client_id"],
                            train_time=wall_times[r["client_id"]],
                            simulated_time=sim_times[r["client_id"]],
                            **r["metrics"], **extra)
                self.history.append(metrics)
                return metrics

        return finalize

    # ------------------------------------------------------------------
    # checkpoint / resume (cfg.checkpoint — repro.checkpoint.store)
    # ------------------------------------------------------------------
    def _maybe_checkpoint(self, completed: int) -> None:
        ck = self.cfg.checkpoint
        if ck.every and completed % ck.every == 0:
            self.save_checkpoint(completed)

    def save_checkpoint(self, completed: int) -> str:
        """Atomically persist everything a fresh ``Trainer`` needs to
        continue from round ``completed``: server params + selection RNG
        (+ any FedBuff buffer, decompressed), round index, history, the
        heterogeneity speed assignments (``speed_ratio`` uses the
        process-randomized ``hash``, so they must be carried explicitly),
        scheduler profiles, and the error-feedback residuals of both
        engines.  The fault sampler is stateless (see
        :class:`FaultInjector`) and needs no persisted state."""
        from repro.checkpoint.store import save_checkpoint

        with jax.profiler.TraceAnnotation("fl.checkpoint",
                                          round=completed - 1):
            state: Dict[str, Any] = {
                "format": 1,
                "round": int(completed),
                "execution": self.cfg.resources.execution,
                "finetune": self.cfg.client.finetune,
                "server": self.server.state_dict(),
                "history": self.history,
                "het_assignment": dict(self.het.assignment),
                "scheduler": {
                    "default_time": float(self.scheduler.default_time),
                    "profiles": {
                        cid: [float(p.time), bool(p.profiled)]
                        for cid, p in self.scheduler.profiles.items()},
                },
                "client_residuals": {
                    cid: jax.tree_util.tree_map(np.asarray, c._residual)
                    for cid, c in self.clients.items()
                    if c._residual is not None},
            }
            if self.engine is not None:
                state["ef"] = self.engine.ef_state()
            ck = self.cfg.checkpoint
            return save_checkpoint(ck.dir, state, step=completed, keep=ck.keep)

    def resume(self, callback: Optional[Callable] = None,
               step: Optional[int] = None) -> Dict[str, Any]:
        """Load the latest (or ``step``) checkpoint from
        ``cfg.checkpoint.dir`` and continue training to completion.

        Synchronous engines continue **bit-identically** to the
        uninterrupted run (every source of randomness is either restored —
        selection RNG, speed assignments, EF residuals — or deterministic:
        data shuffles, the fault sampler), except under a
        ``round_deadline``, whose misses depend on measured wall time.
        The async engine resumes its remaining buffer aggregations from
        the checkpointed model/version; in-flight work at the kill is
        re-dispatched, so its trajectory is equivalent but not
        bit-identical (see docs/faults.md)."""
        from repro.checkpoint.store import load_checkpoint

        state = load_checkpoint(self.cfg.checkpoint.dir, step)
        if state.get("execution") != self.cfg.resources.execution:
            raise ValueError(
                f"checkpoint was written by a "
                f"{state.get('execution')!r}-execution run; this trainer "
                f"uses {self.cfg.resources.execution!r} — resume with the "
                f"same engine")
        if state.get("finetune", "full") != self.cfg.client.finetune:
            raise ValueError(
                f"checkpoint was written by a finetune="
                f"{state.get('finetune', 'full')!r} run; this trainer uses "
                f"finetune={self.cfg.client.finetune!r} — the parameter "
                f"trees are incompatible (LoRA adapters vs full weights)")
        completed = int(state["round"])
        self.server.load_state_dict(state["server"])
        self.server.params = jax.tree_util.tree_map(
            jnp.asarray, self.server.params)
        self.history = list(state.get("history", []))
        self.het.assignment = {str(k): float(v) for k, v in
                               state.get("het_assignment", {}).items()}
        sched = state.get("scheduler", {})
        self.scheduler.default_time = float(
            sched.get("default_time", self.scheduler.default_time))
        for cid, (t, profiled) in sched.get("profiles", {}).items():
            self.scheduler.profiles[str(cid)] = ClientProfile(
                time=float(t), profiled=bool(profiled))
        self._pending_residuals = dict(state.get("client_residuals", {}))
        if self.engine is not None and "ef" in state:
            self.engine.load_ef_state(state["ef"])
        if self.cfg.tracking.enabled:
            from repro.core.config import to_dict
            self.tracker.create_task(self.cfg.task_id, to_dict(self.cfg))
        return self._run(callback, start_round=completed)

    # ------------------------------------------------------------------
    def run(self, callback: Optional[Callable] = None) -> Dict[str, Any]:
        if self.server.params is None:
            self.server.params = self.model.init(
                jax.random.PRNGKey(self.cfg.seed))
        if self.cfg.tracking.enabled:
            from repro.core.config import to_dict
            self.tracker.create_task(self.cfg.task_id, to_dict(self.cfg))
        return self._run(callback, start_round=0)

    def _run(self, callback: Optional[Callable],
             start_round: int) -> Dict[str, Any]:
        """Round loop shared by :meth:`run` (from 0) and :meth:`resume`."""
        if self.cfg.resources.execution == "async":
            from repro.core.async_engine import AsyncEngine
            # the engine appends each aggregation to self.history itself
            # (so periodic checkpoints see it) and sizes its remaining
            # budget from len(history)
            AsyncEngine(self).run()
        else:
            # tracking.round_sync=False runs a one-deep pipeline: round R's
            # metric fetch/finalize is deferred until after round R+1 has
            # been dispatched, so the device never idles on a host sync.
            # Checkpoint rounds force the pending finalize first so that
            # resume stays bit-identical to a synchronous run.
            defer = not self.cfg.tracking.round_sync
            pending: Optional[Callable[[], Dict[str, float]]] = None
            ck = self.cfg.checkpoint
            te = self.cfg.server.test_every
            for r in range(start_round, self.cfg.server.rounds):
                with jax.profiler.StepTraceAnnotation(
                        "fl.round", step_num=r, round=r):
                    fin = self._dispatch_round(r)
                    if pending is not None:
                        pending()
                        pending = None
                    # checkpoint and test rounds must finalize before the next
                    # dispatch: the fused program donates its input params, so
                    # round R+1 consumes the buffers round R's deferred
                    # test()/save would otherwise read
                    eager = (ck.every and (r + 1) % ck.every == 0) or \
                            (te and (r + 1) % te == 0)
                    if defer and not eager:
                        pending = fin
                    else:
                        fin()
                        self._maybe_checkpoint(r + 1)
            if pending is not None:
                pending()
        self.server.finalize()
        summary = {
            "task_id": self.cfg.task_id,
            "rounds": self.cfg.server.rounds,
            "final": self.history[-1] if self.history else {},
            "history": self.history,
            "params": self.server.params,
        }
        if callback is not None:
            callback(summary)
        return summary
