"""Smoke test of the federated round on a TPU: the quickest proof that the
system still starts on the chip.

One process, phases in order; the first failure ends the run with a
non-zero exit and no result line.

1. device — the default backend must be a TPU (no CPU fallback).
2. kernels — each Pallas kernel of the round, compiled on the chip, against
   its ``repro.kernels.ref`` oracle at the ResNet-18 update width
   (16 clients x 11.2M params).
3. main path — ``easyfl.init`` + ``easyfl.run``: full-width CIFAR-10
   ResNet-18, 100 clients, 16 per round, 3 rounds, STC uplink, the fused
   round program with the FedAvg kernel.  It must run as one dispatch and
   one host fetch per round with no retrace after round 1 and stay
   finite.
4. server step — the same stacked ResNet-18 client updates, two steps
   in a row, through the fused program's server half (STC with error
   feedback, FedAvg kernel, apply) and through the staged path
   (``compress_stacked``, einsum ``aggregate_stacked``, ``apply_delta``):
   params within 1e-5.
5. small input — the 3-round STC federation on logistic regression,
   matmuls pinned to f32: fused against staged and against the
   sequential engine (the plain per-client reference) within 1e-5.

With ``--chips 4`` it runs only the ``resources.distributed="data"`` path:
the server step sharded over four chips against the same step on one of
them, at full width, and the small input's rounds through ``easyfl.run``
sharded over four chips against the same rounds on one chip.

Wall and compile time per phase and the process's peak device memory so
far are printed as information.  The last line of stdout is the JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip data-parallel path
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import hashlib
import json
import math
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

COHORT = 16
ROUNDS = 3
TOL = dict(rtol=1e-5, atol=1e-5)
MAIN_CONFIG = {
    "model": "cifar_resnet18", "dataset": "cifar10",
    "data": {"num_clients": 100, "batch_size": 32},
    "server": {"rounds": ROUNDS, "clients_per_round": COHORT,
               "test_every": 0},
    "client": {"local_epochs": 1, "compression": "stc"},
    "resources": {"execution": "batched", "round_fusion": "auto",
                  "aggregation_kernel": True},
}
# Logistic regression keeps rounding at rounding size over 3 rounds of
# local epochs, so 1e-5 tests the whole round's semantics there.
SMALL_CONFIG = {**MAIN_CONFIG, "model": "linear", "dataset": "synthetic"}

_compile_s = collections.Counter()


def _on_duration(name, secs, **_):
    # XLA compile, or load on a persistent-cache hit.  Trace and lowering
    # events nest (a jit inside a jit) and the cache's own events (time
    # saved, retrieval) would count a hit twice, so they are left out.
    if name == "/jax/core/compile/backend_compile_duration":
        _compile_s["total"] += secs


def _peak_hbm_gib() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


@contextlib.contextmanager
def phase(name: str):
    c0, t0 = _compile_s["total"], time.perf_counter()
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] ok wall={time.perf_counter() - t0:.3f}s "
          f"compile={_compile_s['total'] - c0:.3f}s "
          f"peak_hbm={_peak_hbm_gib():.3f}GiB", flush=True)


def check_close(what: str, got, want, **tol) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    print(f"  {what}: max |diff| = {err:.3e}", flush=True)
    np.testing.assert_allclose(got, want, err_msg=what, **(tol or TOL))


def device_phase(want_chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"  platform={d.platform} kind={d.device_kind} count={len(devs)}",
          flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{d.platform!r} devices); nothing was run")
    if len(devs) < want_chips:
        raise SystemExit(f"chip_smoke: --chips {want_chips} needs "
                         f"{want_chips} TPU chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_phase() -> None:
    from repro.kernels import fedavg_agg, ref, resolve_interpret
    from repro.kernels import ops as kops
    from repro.models.registry import get_model

    if resolve_interpret():
        raise AssertionError("kernels would run in the Pallas interpreter")
    model = get_model("cifar_resnet18")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    d = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
    print(f"  update width D={d} (cifar_resnet18 params), N={COHORT}",
          flush=True)
    ku, kw = jax.random.split(jax.random.PRNGKey(0))
    u = jax.random.normal(ku, (COHORT, d), jnp.float32) * 0.01
    w = jax.random.uniform(kw, (COHORT,), jnp.float32, 0.5, 1.5)
    w = w / jnp.sum(w)

    want = ref.fedavg_ref(u, w)
    check_close("fedavg_aggregate", kops.fedavg_aggregate(u, w), want)
    check_close("fedavg_aggregate_tree",
                fedavg_agg.fedavg_aggregate_tree(u, w, fanout=4), want)
    sent, nnz = kops.stc_compress_batched(u, 0.01)
    sent_ref, nnz_ref = ref.stc_batched_ref(u, 0.01)
    check_close("stc_compress_batched", sent, sent_ref)
    check_close("stc_compress_batched nnz", nnz, nnz_ref, rtol=0, atol=0)
    q, scale = kops.int8_roundtrip_batched(u)
    q_ref, scale_ref = ref.int8_roundtrip_batched_ref(u)
    check_close("int8_roundtrip_batched scale", scale, scale_ref)
    check_close("int8_roundtrip_batched", q, q_ref)


def run_rounds(config: dict, **resources) -> dict:
    """One ``easyfl.run`` of ``config`` with resource overrides; returns
    host copies of the final params, the history and the counter deltas."""
    import repro as easyfl
    from repro.core import batched

    cfg = {k: dict(v) if isinstance(v, dict) else v
           for k, v in config.items()}
    cfg["resources"].update(resources)
    easyfl.reset()
    easyfl.init(cfg)
    t0 = (batched.round_trace_count(), batched.dispatch_count(),
          batched.host_sync_count())
    res = easyfl.run()
    t1 = (batched.round_trace_count(), batched.dispatch_count(),
          batched.host_sync_count())
    out = {"params": jax.tree_util.tree_map(np.asarray, res["params"]),
           "history": res["history"],
           "traces": t1[0] - t0[0], "dispatches": t1[1] - t0[1],
           "syncs": t1[2] - t0[2]}
    easyfl.reset()
    del res
    gc.collect()
    for i, h in enumerate(out["history"]):
        print(f"  round {i}: wall={h['wall_time']:.3f}s "
              f"train_loss={h['train_loss']:.8f}", flush=True)
    digest = hashlib.sha256(b"".join(
        np.ascontiguousarray(leaf).tobytes()
        for leaf in jax.tree_util.tree_leaves(out["params"]))).hexdigest()
    # equal digests across runs of the same program: it is deterministic
    print(f"  round-program traces={out['traces']} "
          f"dispatches={out['dispatches']} host_syncs={out['syncs']} "
          f"params_sha256={digest[:16]}", flush=True)
    losses = [h["train_loss"] for h in out["history"]]
    if len(losses) != cfg["server"]["rounds"] or \
            not np.all(np.isfinite(losses)):
        raise AssertionError(f"train losses {losses}")
    for leaf in jax.tree_util.tree_leaves(out["params"]):
        if not np.all(np.isfinite(leaf)):
            raise AssertionError("non-finite params after training")
    return out


def check_trees(what: str, a, b) -> None:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    err = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    print(f"  {what}: max |diff| over params = {err:.3e}", flush=True)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, err_msg=what, **TOL)


def check_params(what: str, a: dict, b: dict) -> None:
    check_trees(what, a["params"], b["params"])


def check_fused(out: dict) -> None:
    if (out["traces"], out["dispatches"], out["syncs"]) != \
            (1, ROUNDS, ROUNDS):
        raise AssertionError(
            "fused round must trace once and run 1 dispatch + 1 host fetch "
            f"per round over {ROUNDS} rounds")


def server_step_program(mesh=None, use_kernel=True):
    """The fused round program's server half, jitted alone: STC with error
    feedback, FedAvg, apply (sharded over ``mesh`` when given)."""
    from repro.core.batched import CLIENT_AXIS, _server_step_fn

    step = _server_step_fn("stc", 0.01, False, 0.0, "flat", 0, use_kernel,
                           1.0, None, mesh)
    if mesh is None:
        return jax.jit(step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    cl = NamedSharding(mesh, P(CLIENT_AXIS))
    rep = NamedSharding(mesh, P())
    ef = NamedSharding(mesh, P(CLIENT_AXIS, None))
    return jax.jit(step, in_shardings=(rep, cl, rep, rep, rep, ef, rep),
                   out_shardings=(rep, cl, cl, ef))


def server_step_inputs(model_name: str):
    """Params, FedAvg weights and two rounds of stacked client updates."""
    from repro.core.aggregation import fedavg_weights
    from repro.models.registry import get_model

    model = get_model(model_name)
    params = model.init(jax.random.PRNGKey(0))
    num_samples = np.random.RandomState(0).randint(400, 800, COHORT)
    weights = jnp.asarray(fedavg_weights(num_samples), jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    updates = []
    for r in range(2):
        keys = jax.random.split(jax.random.PRNGKey(100 + r), len(leaves))
        updates.append(jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, (COHORT,) + leaf.shape, jnp.float32) * 1e-3
            for k, leaf in zip(keys, leaves)]))
    return model, params, num_samples, weights, updates


def run_server_steps(program, params, weights, updates, mesh=None):
    """Two steps of ``program`` from a zero residual store."""
    sizes = [math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(params)]
    ef = tuple(jnp.zeros((COHORT, s), jnp.float32) for s in sizes)
    rows = jnp.arange(COHORT, dtype=jnp.int32)
    ones, no_nan = jnp.ones((COHORT,), jnp.float32), jnp.zeros((COHORT,), bool)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        params, weights, rows, ones, no_nan = jax.device_put(
            (params, weights, rows, ones, no_nan), rep)
        ef = jax.device_put(ef, NamedSharding(mesh, P("clients", None)))
        updates = jax.device_put(updates, NamedSharding(mesh, P("clients")))
    for upd in updates:
        params, ok, nnz, ef = program(params, upd, weights, ones, no_nan,
                                      ef, rows)
    if not bool(jnp.all(ok)) or not nnz:
        raise AssertionError("server step: guard rejected a clean update "
                             "or STC counted no leaves")
    return jax.tree_util.tree_map(np.asarray, params)


def server_step_phase() -> None:
    """Fused server half vs the staged stages on the same updates."""
    from repro.core.aggregation import apply_delta
    from repro.core.batched import BatchedExecutor

    model, params, num_samples, weights, updates = server_step_inputs(
        MAIN_CONFIG["model"])
    fused = run_server_steps(server_step_program(), params, weights, updates)
    ex = BatchedExecutor(model, max_clients=COHORT)
    clients = [types.SimpleNamespace(client_id=f"c{i}") for i in range(COHORT)]
    staged = params
    for upd in updates:
        st = ex.compress_stacked({"updates": upd, "num_samples": num_samples},
                                 clients, "stc", 0.01)
        staged = apply_delta(staged, ex.aggregate_stacked(st))
    check_trees("fused vs staged server step", fused,
                jax.tree_util.tree_map(np.asarray, staged))


def main_path_phase() -> None:
    with phase("main path: fused round, FedAvg kernel"):
        check_fused(run_rounds(MAIN_CONFIG))
    with phase("server step: fused vs staged, same updates"):
        server_step_phase()
    with jax.default_matmul_precision("float32"):
        with phase("small input: fused vs staged vs sequential"):
            fused = run_rounds(SMALL_CONFIG)
            staged = run_rounds(SMALL_CONFIG, round_fusion="off",
                                aggregation_kernel=False)
            sequential = run_rounds(SMALL_CONFIG, execution="sequential",
                                    round_fusion="off",
                                    aggregation_kernel=False)
            check_params("fused vs staged", fused, staged)
            check_params("fused vs sequential", fused, sequential)


def four_chip_phase() -> None:
    from repro.core.batched import build_client_mesh

    with phase("server step: 4-chip sharded vs one chip, same updates"):
        _, params, _, weights, updates = server_step_inputs(
            MAIN_CONFIG["model"])
        mesh = build_client_mesh()
        sharded = run_server_steps(server_step_program(mesh), params,
                                   weights, updates, mesh)
        single = run_server_steps(server_step_program(), params, weights,
                                  updates)
        check_trees("4-chip sharded vs 1-chip server step", sharded, single)
    with jax.default_matmul_precision("float32"):
        with phase("small input: 4-chip sharded vs one chip"):
            sharded = run_rounds(SMALL_CONFIG, distributed="data")
            check_fused(sharded)
            single = run_rounds(SMALL_CONFIG)
            check_params("4-chip sharded vs 1-chip", sharded, single)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    with phase("device"):
        device = device_phase(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"  compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 4:
        four_chip_phase()
    else:
        with phase("kernels"):
            kernel_phase()
        main_path_phase()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
