"""Layer 2: compiled-program contracts for the batched cohort program.

Compiles a small, fixed-shape cohort program through the production
builder (``repro.core.batched.make_cohort_program``) and asserts three
properties that every PR since the batched engine landed has protected by
hand-written tests:

* **retrace budget** — exactly one trace per (bucket, hetero-family)
  combination, zero retraces across rounds (``cohort_trace_count()``);
* **no host transfers** — the post-optimization HLO of the round program
  contains no outfeed/infeed/send/recv or host custom-calls;
* **roofline ratchet** — per-round FLOPs/HBM-bytes from the call-graph
  cost model (``launch.hlo_analysis.analyze_hlo``) must stay within
  ``tolerance`` (default 15%) of ``scripts/roofline_baseline.json``.  A
  PR that bloats the compiled round program fails CI; a PR that shrinks
  it prints a hint to re-baseline (``flcheck --contracts
  --update-baseline``).

The check uses a fixed tiny federation (4 clients, linear model) so it
compiles in seconds; the contracts are about program *structure*, which
the tiny shape already exercises (vmap+scan, donation, masking).

The same federation is then re-compiled with ``client.finetune = "lora"``
semantics (``repro.models.lora.lora_wrap``, rank 2): the adapter-tree
cohort program must meet the same trace budget / zero-retrace /
no-host-transfer contracts.  The roofline ratchet stays on the base
program only — the LoRA program's cost profile is intentionally
different (frozen base hoisted as constants), so ratcheting it against
the full-delta baseline would be meaningless.
"""
from __future__ import annotations

import json
import os
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

#: ops whose presence in the round program means a host round-trip
HOST_TRANSFER_OPS = ("outfeed", "infeed", "send", "recv",
                     "send-done", "recv-done")

#: one (bucket, hetero-family) combination in the fixed federation
TRACE_BUDGET = 1
TOLERANCE = 0.15
BASELINE_RELPATH = os.path.join("scripts", "roofline_baseline.json")

# fixed tiny-federation shapes (changing these invalidates the baseline)
N_CLIENTS = 4
LOCAL_STEPS = 4
BATCH = 8
DIN = 16
CLASSES = 4
POOL_ROWS = 32


@dataclass
class ContractReport:
    traces_first_round: int = 0
    retraces: int = 0
    trace_budget: int = TRACE_BUDGET
    host_transfer_ops: List[str] = field(default_factory=list)
    lora_traces_first_round: int = 0
    lora_retraces: int = 0
    lora_host_transfer_ops: List[str] = field(default_factory=list)
    tree_traces_first_round: int = 0
    tree_retraces: int = 0
    tree_host_transfer_ops: List[str] = field(default_factory=list)
    fused_traces_first_round: int = 0
    fused_retraces: int = 0
    fused_host_transfer_ops: List[str] = field(default_factory=list)
    fused_dispatches_per_round: int = 0
    fused_host_syncs_per_round: int = 0
    fused_flops: float = 0.0
    fused_hbm_bytes: float = 0.0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    baseline: Optional[Dict] = None
    violations: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def format(self) -> str:
        lines = [
            f"contracts: traces={self.traces_first_round} "
            f"(budget {self.trace_budget}), retraces={self.retraces}",
            f"contracts: host transfer ops: "
            f"{self.host_transfer_ops or 'none'}",
            f"contracts: lora cohort traces={self.lora_traces_first_round} "
            f"(budget {self.trace_budget}), "
            f"retraces={self.lora_retraces}, host transfer ops: "
            f"{self.lora_host_transfer_ops or 'none'}",
            f"contracts: hierarchical aggregation "
            f"traces={self.tree_traces_first_round} "
            f"(budget {self.trace_budget}), "
            f"retraces={self.tree_retraces}, host transfer ops: "
            f"{self.tree_host_transfer_ops or 'none'}",
            f"contracts: fused round traces={self.fused_traces_first_round} "
            f"(budget {self.trace_budget}), "
            f"retraces={self.fused_retraces}, "
            f"dispatches/round={self.fused_dispatches_per_round}, "
            f"host syncs/round={self.fused_host_syncs_per_round}, "
            f"host transfer ops: "
            f"{self.fused_host_transfer_ops or 'none'}",
            f"contracts: fused round program flops={self.fused_flops:.3e} "
            f"hbm_bytes={self.fused_hbm_bytes:.3e}",
            f"contracts: round program flops={self.flops:.3e} "
            f"hbm_bytes={self.hbm_bytes:.3e}",
        ]
        if self.baseline:
            lines.append(
                f"contracts: baseline flops={self.baseline['flops']:.3e} "
                f"hbm_bytes={self.baseline['hbm_bytes']:.3e} "
                f"(tolerance {self.baseline.get('tolerance', TOLERANCE)})")
        for v in self.violations:
            lines.append(f"contracts: VIOLATION: {v}")
        lines.append("contracts: " + ("ok" if self.ok else "FAILED"))
        return "\n".join(lines)


def default_baseline_path() -> str:
    from repro.analysis.lint import find_root
    return os.path.join(find_root(os.path.dirname(__file__)),
                        BASELINE_RELPATH)


def _fixed_inputs(model):
    """Deterministic stacked inputs for the fixed tiny federation."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched import CohortVectors
    from repro.core.config import ClientConfig
    from repro.optim import hparams_from_config, sgd_traced

    params = model.init(jax.random.PRNGKey(0))
    _, hp0 = hparams_from_config(ClientConfig(lr=0.1))
    hp = type(hp0)(*(np.full((N_CLIENTS,), getattr(hp0, f), np.float32)
                     for f in type(hp0)._fields))
    vec = CohortVectors(mu=np.zeros((N_CLIENTS,), np.float32),
                        max_norm=np.zeros((N_CLIENTS,), np.float32),
                        hp=hp)
    opt = sgd_traced(use_momentum=True, use_nesterov=False)

    rng = np.random.RandomState(0)
    x = rng.randn(N_CLIENTS, POOL_ROWS, DIN).astype(np.float32)
    y = rng.randint(0, CLASSES, size=(N_CLIENTS, POOL_ROWS)) \
        .astype(np.int32)
    idx = rng.randint(0, POOL_ROWS,
                      size=(N_CLIENTS, LOCAL_STEPS, BATCH)).astype(np.int32)
    n_steps = np.full((N_CLIENTS,), LOCAL_STEPS, np.int32)

    def args():
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None],
                                       (N_CLIENTS,) + p.shape).copy(),
            params)
        return (stacked, jnp.asarray(x), jnp.asarray(y), jnp.asarray(idx),
                jnp.asarray(n_steps),
                jax.tree_util.tree_map(jnp.asarray, vec), params)

    return opt, args


def _host_transfer_ops(hlo: str) -> List[str]:
    from repro.launch.hlo_analysis import parse_hlo

    found = []
    for comp in parse_hlo(hlo).values():
        for ins in comp.instrs:
            if ins.op in HOST_TRANSFER_OPS:
                found.append(f"{ins.op} ({ins.name})")
            elif ins.op == "custom-call" and "host" in ins.tail.lower():
                found.append(f"custom-call ({ins.name})")
    return found


def check_contracts(baseline_path: Optional[str] = None,
                    update_baseline: bool = False,
                    trace_budget: int = TRACE_BUDGET,
                    tolerance: float = TOLERANCE) -> ContractReport:
    """Compile the cohort program and check all three contracts.

    ``update_baseline=True`` rewrites the roofline baseline instead of
    gating against it (the re-baseline path after an intentional program
    change).  Returns a :class:`ContractReport`; ``report.ok`` is the
    gate verdict.
    """
    import jax

    from repro.core import batched
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.models.small import linear_model

    report = ContractReport(trace_budget=trace_budget)
    model = linear_model(din=DIN, classes=CLASSES)
    opt, args = _fixed_inputs(model)

    # fresh program: the budget counts traces of THIS build, regardless of
    # what else the process compiled before
    batched.make_cohort_program.cache_clear()
    t0 = batched.cohort_trace_count()
    program = batched.make_cohort_program(model, opt, LOCAL_STEPS,
                                          use_prox=False, use_clip=False,
                                          mesh=None)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donated.*")
        out = program(*args())
        jax.block_until_ready(out)
        report.traces_first_round = batched.cohort_trace_count() - t0
        out = program(*args())         # second round, identical shapes
        jax.block_until_ready(out)
    report.retraces = (batched.cohort_trace_count() - t0
                       - report.traces_first_round)
    if report.traces_first_round > trace_budget:
        report.violations.append(
            f"retrace budget: {report.traces_first_round} trace(s) for one "
            f"(bucket, hetero-family) combination, budget is {trace_budget}")
    if report.retraces != 0:
        report.violations.append(
            f"retrace budget: {report.retraces} retrace(s) across rounds "
            f"at fixed shapes (expected 0)")

    hlo = program.lower(*args()).compile().as_text()
    report.host_transfer_ops = _host_transfer_ops(hlo)
    if report.host_transfer_ops:
        report.violations.append(
            "host transfers in the round program: "
            + ", ".join(report.host_transfer_ops))

    # same contracts on the LoRA-adapter cohort program (structure only —
    # the roofline ratchet below gates the base program exclusively)
    from repro.models.lora import lora_wrap
    lmodel = lora_wrap(model, model.init(jax.random.PRNGKey(0)), rank=2)
    _, largs = _fixed_inputs(lmodel)
    lt0 = batched.cohort_trace_count()
    lprogram = batched.make_cohort_program(lmodel, opt, LOCAL_STEPS,
                                           use_prox=False, use_clip=False,
                                           mesh=None)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donated.*")
        lout = lprogram(*largs())
        jax.block_until_ready(lout)
        report.lora_traces_first_round = batched.cohort_trace_count() - lt0
        lout = lprogram(*largs())      # second round, identical shapes
        jax.block_until_ready(lout)
    report.lora_retraces = (batched.cohort_trace_count() - lt0
                            - report.lora_traces_first_round)
    if report.lora_traces_first_round > trace_budget:
        report.violations.append(
            f"retrace budget (lora): {report.lora_traces_first_round} "
            f"trace(s) for the adapter cohort, budget is {trace_budget}")
    if report.lora_retraces != 0:
        report.violations.append(
            f"retrace budget (lora): {report.lora_retraces} retrace(s) "
            f"across rounds at fixed shapes (expected 0)")
    report.lora_host_transfer_ops = _host_transfer_ops(
        lprogram.lower(*largs()).compile().as_text())
    if report.lora_host_transfer_ops:
        report.violations.append(
            "host transfers in the lora round program: "
            + ", ".join(report.lora_host_transfer_ops))

    # hierarchical streaming aggregation: the reduction-tree program
    # (kernels.fedavg_agg._tree_padded) must meet the same structural
    # contracts — one trace for a fixed (cohort, fanout, tiling), zero
    # retraces across rounds, no host transfers.  The roofline ratchet
    # stays on the base cohort program only.
    from repro.kernels import fedavg_agg
    import jax.numpy as jnp
    agg_rng = np.random.RandomState(1)
    agg_u = jnp.asarray(agg_rng.randn(16, 256).astype(np.float32))
    agg_w = jnp.asarray(
        (np.ones(16) / 16).astype(np.float32))
    tree_args = (agg_u, agg_w)
    tree_kw = dict(fanout=4, use_kernel=True, interpret=None,
                   tile_d=fedavg_agg.TILE_D, tile_n=fedavg_agg.TILE_N)
    fedavg_agg._tree_padded.clear_cache()
    tt0 = fedavg_agg.tree_trace_count()
    jax.block_until_ready(fedavg_agg._tree_padded(*tree_args, **tree_kw))
    report.tree_traces_first_round = fedavg_agg.tree_trace_count() - tt0
    jax.block_until_ready(fedavg_agg._tree_padded(*tree_args, **tree_kw))
    report.tree_retraces = (fedavg_agg.tree_trace_count() - tt0
                            - report.tree_traces_first_round)
    if report.tree_traces_first_round > trace_budget:
        report.violations.append(
            f"retrace budget (hierarchical agg): "
            f"{report.tree_traces_first_round} trace(s) for one "
            f"(cohort, fanout) combination, budget is {trace_budget}")
    if report.tree_retraces != 0:
        report.violations.append(
            f"retrace budget (hierarchical agg): {report.tree_retraces} "
            f"retrace(s) across rounds at fixed shapes (expected 0)")
    report.tree_host_transfer_ops = _host_transfer_ops(
        fedavg_agg._tree_padded.lower(
            *tree_args, **tree_kw).compile().as_text())
    if report.tree_host_transfer_ops:
        report.violations.append(
            "host transfers in the hierarchical aggregation program: "
            + ", ".join(report.tree_host_transfer_ops))

    # whole-round fusion (resources.round_fusion="auto"): the single
    # program per round — train + (compression) + fault weighting + FedAvg
    # + server apply — must trace once, never retrace at fixed shapes,
    # contain no host transfers, and execute as exactly ONE dispatch with
    # ONE batched device->host fetch per round at the executor level.
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData

    from repro.core.aggregation import fedavg_weights

    weights = jnp.asarray(fedavg_weights([1] * N_CLIENTS))
    fmask = jnp.ones((N_CLIENTS,), jnp.float32)
    nanm = jnp.zeros((N_CLIENTS,), bool)
    ef_rows = jnp.zeros((N_CLIENTS,), jnp.int32)
    # host snapshot: the fused program donates its params argument, so
    # every call needs a fresh device copy
    gp_host = jax.tree_util.tree_map(np.asarray, args()[6])

    def fused_args():
        a = args()          # (stacked, x, y, idx, n_steps, vec, params)
        gp = jax.tree_util.tree_map(jnp.asarray, gp_host)
        return (gp, a[1], a[2], a[3], a[4], a[5], weights, fmask, nanm,
                (), ef_rows)

    batched.make_round_program.cache_clear()
    ft0 = batched.round_trace_count()
    fprogram = batched.make_round_program(model, opt, LOCAL_STEPS,
                                          use_prox=False, use_clip=False,
                                          mesh=None)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donated.*")
        fout = fprogram(*fused_args())
        jax.block_until_ready(fout)
        report.fused_traces_first_round = batched.round_trace_count() - ft0
        fout = fprogram(*fused_args())  # second round, identical shapes
        jax.block_until_ready(fout)
    report.fused_retraces = (batched.round_trace_count() - ft0
                             - report.fused_traces_first_round)
    if report.fused_traces_first_round > trace_budget:
        report.violations.append(
            f"retrace budget (fused round): "
            f"{report.fused_traces_first_round} trace(s) for one "
            f"(bucket, hetero-family) combination, budget is {trace_budget}")
    if report.fused_retraces != 0:
        report.violations.append(
            f"retrace budget (fused round): {report.fused_retraces} "
            f"retrace(s) across rounds at fixed shapes (expected 0)")
    fhlo = fprogram.lower(*fused_args()).compile().as_text()
    report.fused_host_transfer_ops = _host_transfer_ops(fhlo)
    if report.fused_host_transfer_ops:
        report.violations.append(
            "host transfers in the fused round program: "
            + ", ".join(report.fused_host_transfer_ops))

    # executor level: a fused round is ONE dispatch + ONE batched fetch
    ex_rng = np.random.RandomState(2)
    ex_clients = []
    for i in range(N_CLIENTS):
        data = ClientData(ex_rng.randn(POOL_ROWS, DIN).astype(np.float32),
                          ex_rng.randint(0, CLASSES, POOL_ROWS)
                          .astype(np.int32))
        ex_clients.append(Client(f"c{i}", model, data,
                                 ClientConfig(lr=0.1, local_epochs=1),
                                 batch_size=BATCH))
    from repro.core.batched import BatchedExecutor
    executor = BatchedExecutor(model, max_clients=N_CLIENTS)
    executor.run_round_fused(ex_clients, model.init(jax.random.PRNGKey(0)),
                             round_id=0)        # warm-up (compile round)
    d0, h0 = batched.dispatch_count(), batched.host_sync_count()
    executor.run_round_fused(ex_clients, model.init(jax.random.PRNGKey(1)),
                             round_id=1)
    report.fused_dispatches_per_round = batched.dispatch_count() - d0
    report.fused_host_syncs_per_round = batched.host_sync_count() - h0
    if report.fused_dispatches_per_round != 1:
        report.violations.append(
            f"fused round dispatch count: "
            f"{report.fused_dispatches_per_round} (expected exactly 1)")
    if report.fused_host_syncs_per_round != 1:
        report.violations.append(
            f"fused round host-sync count: "
            f"{report.fused_host_syncs_per_round} (expected exactly 1 "
            f"batched device->host fetch)")

    fcost = analyze_hlo(fhlo)
    report.fused_flops = fcost.flops
    report.fused_hbm_bytes = fcost.hbm_bytes

    cost = analyze_hlo(hlo)
    report.flops = cost.flops
    report.hbm_bytes = cost.hbm_bytes

    path = baseline_path or default_baseline_path()
    if update_baseline:
        baseline = {
            "flops": cost.flops,
            "hbm_bytes": cost.hbm_bytes,
            "fused_flops": fcost.flops,
            "fused_hbm_bytes": fcost.hbm_bytes,
            "tolerance": tolerance,
            "program": {
                "model": f"linear(din={DIN}, classes={CLASSES})",
                "clients": N_CLIENTS, "local_steps": LOCAL_STEPS,
                "batch": BATCH,
            },
            "jax": jax.__version__,
        }
        with open(path, "w") as f:
            json.dump(baseline, f, indent=1)
            f.write("\n")
        report.baseline = baseline
        return report

    if not os.path.exists(path):
        report.violations.append(
            f"no roofline baseline at {path}; record one with "
            f"'flcheck --contracts --update-baseline'")
        return report
    with open(path) as f:
        report.baseline = json.load(f)
    tol = report.baseline.get("tolerance", tolerance)
    for key, value in (("flops", cost.flops),
                       ("hbm_bytes", cost.hbm_bytes),
                       ("fused_flops", fcost.flops),
                       ("fused_hbm_bytes", fcost.hbm_bytes)):
        base = report.baseline.get(key, 0.0)
        if base and value > base * (1.0 + tol):
            report.violations.append(
                f"roofline ratchet: round-program {key} {value:.3e} exceeds "
                f"baseline {base:.3e} by more than {tol:.0%} — shrink the "
                f"program or re-baseline with an explanation "
                f"(--update-baseline)")
    return report
