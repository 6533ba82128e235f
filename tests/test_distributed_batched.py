"""Shard-count invariance for the mesh-sharded batched engine
(``resources.distributed = "data"``).

The heavy checks run in one subprocess owning
``--xla_force_host_platform_device_count=8``:

* executor level — a 1-device mesh must reproduce the plain batched path
  **bit-for-bit**; 2/4/8-way meshes must agree numerically;
* sharded FedAvg aggregation (per-shard partials + psum epilogue) vs the
  jnp oracle at every shard count;
* end-to-end ``easyfl.run()`` parity: distributed history/params match the
  batched run.

The loud-failure modes (bad ``distributed`` value, no devices for the
mesh, sequential+distributed) are checked in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np

    from repro.core.batched import BatchedExecutor, build_client_mesh
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData
    from repro.kernels import ref
    from repro.kernels.fedavg_agg import fedavg_aggregate_sharded
    from repro.models.small import linear_model

    assert len(jax.devices()) == 8, jax.devices()

    # ---------------- executor-level invariance ----------------
    model = linear_model()
    rng = np.random.RandomState(0)
    clients = []
    for i, n in enumerate([40, 64, 33, 50, 48]):   # unbalanced cohort
        data = ClientData(rng.randn(n, 64).astype(np.float32),
                          rng.randint(0, 10, n).astype(np.int32))
        clients.append(Client(f"c{i}", model, data,
                              ClientConfig(local_epochs=2, lr=0.1),
                              batch_size=16))
    params = model.init(jax.random.PRNGKey(0))

    def stacked_result(executor):
        st = executor.run_cohort_stacked(clients, params, round_id=3)
        leaves = [np.asarray(l) for l in
                  jax.tree_util.tree_leaves(st["updates"])]
        return leaves, st["loss"], st["acc"]

    base_leaves, base_loss, base_acc = stacked_result(BatchedExecutor(model, max_clients=64))

    for k in (1, 2, 4, 8):
        ex = BatchedExecutor(model, max_clients=64, distributed="data",
                             devices=jax.devices()[:k])
        assert ex.mesh.size == k
        leaves, loss, acc = stacked_result(ex)
        if k == 1:
            for a, b in zip(base_leaves, leaves):
                assert np.array_equal(a, b), "1-device mesh not bit-for-bit"
            assert np.array_equal(base_loss, loss)
            assert np.array_equal(base_acc, acc)
        else:
            for a, b in zip(base_leaves, leaves):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(base_loss, loss, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(base_acc, acc, rtol=1e-5, atol=1e-6)
    print("EXEC-OK")

    # ---------------- sharded aggregation vs oracle ----------------
    key = jax.random.PRNGKey(1)
    u = jax.random.normal(key, (37, 700))
    w = jax.nn.softmax(jax.random.normal(key, (37,)))
    exp = np.asarray(ref.fedavg_ref(u, w))
    for k in (1, 2, 4, 8):
        mesh = build_client_mesh(jax.devices()[:k])
        out = np.asarray(fedavg_aggregate_sharded(u, w, mesh))
        np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-4)
    print("AGG-OK")

    # ---------------- end-to-end parity ----------------
    import repro as easyfl

    def run(resources):
        easyfl.reset()
        easyfl.init({
            "model": "linear", "dataset": "synthetic",
            "data": {"num_clients": 12, "batch_size": 32,
                     "unbalanced": True, "unbalanced_sigma": 1.0},
            "server": {"rounds": 3, "clients_per_round": 5},
            "client": {"local_epochs": 2, "lr": 0.1},
            "resources": resources,
        })
        res = easyfl.run()
        easyfl.reset()
        return res

    rb = run({"execution": "batched"})
    rd = run({"execution": "batched", "distributed": "data"})
    for a, b in zip(jax.tree_util.tree_leaves(rb["params"]),
                    jax.tree_util.tree_leaves(rd["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        [h["train_loss"] for h in rb["history"]],
        [h["train_loss"] for h in rd["history"]], rtol=1e-4)
    assert ([h["comm_up_bytes"] for h in rb["history"]]
            == [h["comm_up_bytes"] for h in rd["history"]])
    print("E2E-OK")
""")


@pytest.mark.slow
def test_shard_count_invariance_and_e2e_parity():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    for marker in ("EXEC-OK", "AGG-OK", "E2E-OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr[-2000:])


def test_distributed_needs_devices():
    from repro.core.batched import BatchedExecutor
    from repro.models.small import linear_model

    with pytest.raises(ValueError, match="no.*devices|devices.*none|at least one"):
        BatchedExecutor(linear_model(), max_clients=64, distributed="data",
                        devices=[])


def test_bad_distributed_value_rejected():
    import repro as easyfl

    easyfl.reset()
    easyfl.init({"model": "linear", "dataset": "synthetic",
                 "resources": {"execution": "batched",
                               "distributed": "bogus"}})
    with pytest.raises(ValueError, match="unknown distributed"):
        easyfl.run()
    easyfl.reset()


def test_distributed_requires_batched_execution():
    import repro as easyfl

    easyfl.reset()
    easyfl.init({"model": "linear", "dataset": "synthetic",
                 "resources": {"execution": "sequential",
                               "distributed": "data"}})
    with pytest.raises(ValueError, match="batched"):
        easyfl.run()
    easyfl.reset()


def test_distributed_single_device_in_process():
    """distributed="data" must work (and match batched) on the default
    1-device CPU host — the degenerate mesh."""
    import jax
    import numpy as np

    import repro as easyfl

    def run(resources):
        easyfl.reset()
        easyfl.init({
            "model": "linear", "dataset": "synthetic",
            "data": {"num_clients": 8, "batch_size": 32},
            "server": {"rounds": 2, "clients_per_round": 4},
            "client": {"local_epochs": 1, "lr": 0.1},
            "resources": resources,
        })
        res = easyfl.run()
        easyfl.reset()
        return res

    rb = run({"execution": "batched"})
    rd = run({"execution": "batched", "distributed": "data"})
    for a, b in zip(jax.tree_util.tree_leaves(rb["params"]),
                    jax.tree_util.tree_leaves(rd["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
