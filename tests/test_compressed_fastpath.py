"""Compressed rounds on the batched/sharded/async fast path.

* batched STC/int8 kernels vs their jnp oracles and — per client row —
  vs the sequential compression *stage* (bitwise for int8);
* end-to-end parity: in-program compressed rounds (error feedback carried
  across >= 3 rounds through the executor's residual store) match the
  sequential ``STCClient`` / built-in compression path to 1e-5, for
  synchronous batched rounds, async dispatch waves (degenerate case), and
  a forced 8-device mesh;
* fast-path shape: no ``"update"`` key gathers to host, payload bytes come
  from the in-program per-client nnz, zero cohort-program retraces at
  fixed bucket shapes;
* stage *overrides* (``STCClient``) still fall back to the gathering path;
* the device-side cohort-data cache reuses stacked x/y across rounds.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro as easyfl
from repro.core import compression as comp
from repro.kernels import ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# batched kernels vs oracles vs the sequential stage
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(1, 640), (5, 9000), (16, 8192), (7, 100)])
def test_stc_batched_kernel_matches_ref_and_stage(n, d):
    x = jax.random.normal(jax.random.PRNGKey(n * 100 + d), (n, d))
    out, nnz = ops.stc_compress_batched(x, 0.05)
    ro, rn = ref.stc_batched_ref(x, 0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rn))
    for i in range(n):        # per client row == the sequential stage
        st = comp.stc_compress_array(x[i], 0.05)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(st.data),
                                   rtol=1e-5, atol=1e-6)
        assert int(nnz[i]) == int(st.nnz)


@pytest.mark.parametrize("n,d", [(3, 640), (6, 9000), (1, 64)])
def test_int8_batched_bitwise_matches_sequential_stage(n, d):
    x = jax.random.normal(jax.random.PRNGKey(n + d), (n, d)) * 3.0
    sent, scale = ops.int8_roundtrip_batched(x)
    ro, rs = ref.int8_roundtrip_batched_ref(x)
    assert np.array_equal(np.asarray(sent), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(rs))
    for i in range(n):        # per row bit-identical to the eager stage
        seq = comp.decompress_array(comp.int8_compress_array(x[i]))
        assert np.array_equal(np.asarray(sent[i]), np.asarray(seq))


def test_stc_stage_matches_dense_kernel():
    """stage == kernel: the built-in stc compressor is tile-local and
    bit-matches the Pallas bisection (real-count targets incl. the padded
    last tile)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (9000,))
    st = comp.stc_compress_array(x, 0.02)
    k = ops.stc_compress(x, 0.02)
    np.testing.assert_allclose(np.asarray(st.data), np.asarray(k),
                               rtol=1e-5, atol=1e-6)
    assert int(st.nnz) == int((np.asarray(k) != 0).sum())


def test_stc_small_tensor_budget_not_inflated_by_padding():
    """Per-tile targets count real elements: a 2048-element tensor at 5%
    keeps ~102 entries, not 5% of the padded 8192-tile."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2048,))
    st = comp.stc_compress_array(x, 0.05)
    assert abs(int(st.nnz) - round(0.05 * 2048)) <= 1


# ---------------------------------------------------------------------------
# payload accounting (batched nnz host sync)
# ---------------------------------------------------------------------------


def test_payload_bytes_many_matches_per_tree():
    trees = []
    for i in range(4):
        k1, k2 = jax.random.split(jax.random.PRNGKey(i))
        t = {"a": jax.random.normal(k1, (64, 32)),
             "b": jax.random.normal(k2, (1000,))}
        trees.append(comp.compress(t, "stc", 0.05) if i % 2
                     else comp.compress(t, "int8"))
    many = comp.payload_bytes_many(trees)
    assert many == [comp.payload_bytes(t) for t in trees]


# ---------------------------------------------------------------------------
# end-to-end fast-path parity
# ---------------------------------------------------------------------------


def _run(execution, client_over=None, client_cls=None, resources=None):
    easyfl.reset()
    easyfl.init({
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 12, "batch_size": 32},
        "server": {"rounds": 3, "clients_per_round": 5},
        "client": {"local_epochs": 2, "lr": 0.1, **(client_over or {})},
        "resources": {"execution": execution, **(resources or {})},
    })
    if client_cls is not None:
        easyfl.register_client(client_cls)
    res = easyfl.run()
    easyfl.reset()
    return res


def _assert_equivalent(rs, rb, bytes_exact=True):
    for a, b in zip(jax.tree_util.tree_leaves(rs["params"]),
                    jax.tree_util.tree_leaves(rb["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        [h["train_loss"] for h in rs["history"]],
        [h["train_loss"] for h in rb["history"]], rtol=1e-4)
    if bytes_exact:
        assert ([h["comm_up_bytes"] for h in rs["history"]]
                == [h["comm_up_bytes"] for h in rb["history"]])


def test_batched_stc_fastpath_matches_sequential_stcclient():
    """3 rounds of in-program STC (residual store carried round-over-round)
    vs the sequential STCClient stage-override path: same trajectory AND
    the same nnz-derived wire bytes."""
    from repro.core.strategies.stc import STCClient

    over = {"compression": "stc", "stc_sparsity": 0.05}
    _assert_equivalent(_run("sequential", over, STCClient),
                       _run("batched", over))


def test_batched_int8_fastpath_matches_sequential():
    over = {"compression": "int8"}
    _assert_equivalent(_run("sequential", over), _run("batched", over))


def test_async_stc_waves_match_batched_degenerate():
    """Degenerate async (K = max_concurrency = C, uniform speeds) with
    in-program STC: per-wave compression with residuals keyed by client id
    across waves must reproduce the synchronous batched trajectory."""
    over = {"compression": "stc", "stc_sparsity": 0.05}
    _assert_equivalent(_run("batched", over), _run("async", over),
                       bytes_exact=False)


def _make_trainer(method="stc", client_cls=None):
    from repro.core.client import Client
    from repro.core.config import Config
    from repro.core.rounds import Trainer
    from repro.core.server import Server
    from repro.data.fed_data import build_federated_data
    from repro.models.registry import get_model

    cfg = Config.make({
        "model": "linear",
        "data": {"dataset": "synthetic", "num_clients": 8, "batch_size": 32},
        "server": {"rounds": 3, "clients_per_round": 4, "test_every": 0},
        "client": {"local_epochs": 1, "lr": 0.1, "compression": method,
                   "stc_sparsity": 0.05},
        "resources": {"execution": "batched"},
        "tracking": {"enabled": False},
    })
    model = get_model(cfg.model)
    fed = build_federated_data(cfg.data)
    trainer = Trainer(cfg, model, fed, server=Server(model, cfg, fed.test),
                      client_cls=client_cls or Client)
    trainer.server.params = model.init(jax.random.PRNGKey(cfg.seed))
    return trainer


def test_fastpath_no_update_gather_payload_from_nnz():
    """The compressed fast path aggregates in place: results carry
    nnz-derived payload bytes but no \"update\" key, and the executor's
    residual store holds every selected client."""
    trainer = _make_trainer("stc")
    selected = trainer.server.selection(trainer.fed_data.client_ids, 0)
    payload = trainer.server.distribution(selected)
    results, aggregated, finish = trainer._run_batched(selected, payload, 0)
    assert aggregated is True
    finish()                # the round's accounting, as its finalize runs it
    dense = sum(int(np.prod(l.shape)) * 4 for l in
                jax.tree_util.tree_leaves(trainer.server.params))
    for res in results:
        assert "update" not in res
        assert 0 < res["payload_bytes"] < dense
    assert set(selected) <= set(trainer.engine._ef_rows)


def test_fastpath_zero_retraces_across_compressed_rounds():
    from repro.core.batched import cohort_trace_count

    trainer = _make_trainer("stc")
    trainer.run_round(0)                     # warm-up (compile)
    before = cohort_trace_count()
    for r in range(1, 3):
        trainer.run_round(r)
    assert cohort_trace_count() == before, (
        "compressed rounds must not retrace the cohort program at fixed "
        "bucket shapes")


def test_stage_override_still_falls_back_to_gathering():
    """STCClient overrides the compression stage — the engine cannot see
    inside it, so it must gather per-client updates and leave the
    executor's residual store untouched (the override keeps its own
    Client._residual)."""
    from repro.core.strategies.stc import STCClient

    trainer = _make_trainer("stc", client_cls=STCClient)
    selected = trainer.server.selection(trainer.fed_data.client_ids, 0)
    payload = trainer.server.distribution(selected)
    results, aggregated, _ = trainer._run_batched(selected, payload, 0)
    assert aggregated is False
    assert all("update" in r for r in results)
    assert trainer.engine._ef_rows == {}
    assert all(trainer.clients[c]._residual is not None for c in selected)


def _pool_clients(model, n=4, samples=40):
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData

    rng = np.random.RandomState(0)
    return [Client(f"c{i}", model,
                   ClientData(rng.randn(samples, 64).astype(np.float32),
                              rng.randint(0, 10, samples).astype(np.int32)),
                   ClientConfig(local_epochs=1, lr=0.1), batch_size=16)
            for i in range(n)]


def test_sync_aggregation_override_gets_compressed_tensors():
    """A synchronous Server.aggregation override must keep receiving the
    per-client stage's CompressedTensor pytrees (gathering fallback) —
    in-program compression would hand it dense arrays instead."""
    from repro.core.server import Server

    seen = []

    class InspectingServer(Server):
        def aggregation(self, results):
            seen.extend(jax.tree_util.tree_leaves(
                results[0]["update"],
                is_leaf=lambda x: isinstance(x, comp.CompressedTensor)))
            super().aggregation(results)

    trainer = _make_trainer("stc")
    trainer.server = InspectingServer(trainer.model, trainer.cfg,
                                      trainer.fed_data.test)
    trainer.server.params = trainer.model.init(jax.random.PRNGKey(0))
    trainer.run_round(0)
    assert any(isinstance(l, comp.CompressedTensor) for l in seen)
    assert trainer.engine._ef_rows == {}     # residuals stay per client


def test_cohort_data_pool_reuses_device_buffers():
    """Each client's x/y rows upload host->device once; later rounds —
    including *reordered* cohorts, the default random-permutation
    selection — gather from the pool without re-uploading, and results
    stay identical to a cold executor."""
    from repro.core.batched import BatchedExecutor
    from repro.models.small import linear_model

    model = linear_model()
    clients = _pool_clients(model)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    ex.run_cohort_stacked(clients, params, round_id=0)
    xd = ex._data_pool["x"]
    st_warm = ex.run_cohort_stacked(clients, params, round_id=1)
    assert ex._data_pool["x"] is xd               # no re-upload
    # permuted selection order: still a pure pool gather
    ex.run_cohort_stacked(clients[::-1], params, round_id=2)
    assert ex._data_pool["x"] is xd
    cold = BatchedExecutor(model, max_clients=64).run_cohort_stacked(clients, params,
                                                     round_id=1)
    for a, b in zip(jax.tree_util.tree_leaves(st_warm["updates"]),
                    jax.tree_util.tree_leaves(cold["updates"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_cohort_data_pool_permutation_matches_per_client():
    """A reordered cohort slices each client's own data out of the pool:
    per-client updates must be identical across orderings."""
    from repro.core.batched import BatchedExecutor
    from repro.models.small import linear_model

    model = linear_model()
    clients = _pool_clients(model)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    fwd = ex.run_cohort(clients, params, round_id=1)
    rev = ex.run_cohort(clients[::-1], params, round_id=1)
    for c, res in zip(clients, fwd):
        mate = rev[len(clients) - 1 - clients.index(c)]
        for a, b in zip(jax.tree_util.tree_leaves(res["update"]),
                        jax.tree_util.tree_leaves(mate["update"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_data_pool_invalidate_rereads_mutated_data():
    """Mutating a client's dataset mid-run needs invalidate_data; after
    the call the fast path matches a cold executor on the new data."""
    from repro.core.batched import BatchedExecutor
    from repro.models.small import linear_model

    model = linear_model()
    clients = _pool_clients(model, n=2)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    ex.run_cohort_stacked(clients, params, round_id=0)
    clients[0].data.x[:] = clients[0].data.x[::-1]      # in-place mutation
    ex.invalidate_data(clients[0].client_id)
    warm = ex.run_cohort_stacked(clients, params, round_id=1)
    cold = BatchedExecutor(model, max_clients=64).run_cohort_stacked(clients, params,
                                                     round_id=1)
    for a, b in zip(jax.tree_util.tree_leaves(warm["updates"]),
                    jax.tree_util.tree_leaves(cold["updates"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_data_pool_bounded_lru_eviction():
    """The device tier of the data pool is bounded: least-recently-used
    rows are evicted (dropped — data recomputes from ``c.data``), most
    recent survive, and a readmitted client trains identically."""
    from repro.core.batched import BatchedExecutor
    from repro.models.small import linear_model

    model = linear_model()
    clients = _pool_clients(model, n=5)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    ex.DATA_POOL_MAX_CLIENTS = 3
    ex.run_cohort_stacked(clients[:3], params, round_id=0)
    assert set(ex._data_pool["rows"]) == {"c0", "c1", "c2"}
    ex.run_cohort_stacked(clients[3:], params, round_id=0)   # exceeds bound
    # LRU: c0/c1 evicted, the most recent survivors stay resident
    assert set(ex._data_pool["rows"]) == {"c2", "c3", "c4"}
    assert ex._pool.stats["evictions"] == 2
    # evicted client readmits via the recompute path, bit-identically
    warm = ex.run_cohort_stacked(clients[:2], params, round_id=1)
    cold = BatchedExecutor(model, max_clients=64).run_cohort_stacked(clients[:2], params,
                                                     round_id=1)
    for a, b in zip(jax.tree_util.tree_leaves(warm["updates"]),
                    jax.tree_util.tree_leaves(cold["updates"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# forced 8-device mesh: compressed rounds stay on the sharded fast path
# ---------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np

    from repro.core.batched import build_client_mesh
    from repro.kernels import ops
    from repro.kernels.stc_topk import stc_compress_batched_sharded
    from repro.kernels.quant import int8_roundtrip_batched_sharded

    assert len(jax.devices()) == 8, jax.devices()

    # ---------------- sharded kernels vs unsharded ----------------
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 9000))
    base_out, base_nnz = ops.stc_compress_batched(x, 0.05)
    base_sent, _ = ops.int8_roundtrip_batched(x)
    for k in (1, 2, 4, 8):
        mesh = build_client_mesh(jax.devices()[:k])
        out, nnz = stc_compress_batched_sharded(x, 0.05, mesh)
        assert np.array_equal(np.asarray(out), np.asarray(base_out)), k
        assert np.array_equal(np.asarray(nnz), np.asarray(base_nnz)), k
        sent, _ = int8_roundtrip_batched_sharded(x, mesh)
        assert np.array_equal(np.asarray(sent), np.asarray(base_sent)), k
    print("KERNELS-OK")

    # ---------------- e2e: sharded compressed fast path ----------------
    import repro as easyfl

    def run(resources):
        easyfl.reset()
        easyfl.init({
            "model": "linear", "dataset": "synthetic",
            "data": {"num_clients": 12, "batch_size": 32},
            "server": {"rounds": 3, "clients_per_round": 5},
            "client": {"local_epochs": 2, "lr": 0.1,
                       "compression": "stc", "stc_sparsity": 0.05},
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
    # compressed wire accounting survives sharding (nnz flips from f32
    # noise across device layouts stay tiny)
    ub, ud = (np.array([h["comm_up_bytes"] for h in r["history"]])
              for r in (rb, rd))
    assert np.abs(ub - ud).max() <= 0.02 * ub.max() + 16, (ub, ud)
    print("E2E-OK")
""")


@pytest.mark.slow
def test_compressed_fastpath_on_forced_8device_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    for marker in ("KERNELS-OK", "E2E-OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr[-2000:])
