"""Batched execution engine + chunked streaming FedAvg kernel.

* chunked Pallas kernel vs the jnp einsum oracle for D not a multiple of
  TILE_D and N in {1, 7, 100, 200} (bucket-padding correctness, padded
  weights still summing to 1);
* batched-vs-sequential engine equivalence: same params and metrics to
  ~1e-5 over 3 rounds, including FedProx and STC clients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro as easyfl
from repro.kernels import ops, ref
from repro.kernels.fedavg_agg import TILE_N, bucket_clients, pad_cohort


# ---------------------------------------------------------------------------
# chunked FedAvg kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 100, 200])
@pytest.mark.parametrize("d", [100, 2048, 5000])  # 100, 5000: not tile-aligned
def test_chunked_kernel_matches_oracle(n, d):
    key = jax.random.PRNGKey(n * 10000 + d)
    u = jax.random.normal(key, (n, d))
    w = jax.nn.softmax(jax.random.normal(key, (n,)))
    out = ops.fedavg_aggregate(u, w)
    exp = ref.fedavg_ref(u, w)
    assert out.shape == (d,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n", [1, 7, 100, 200])
def test_bucket_padding_preserves_weight_sum(n):
    u = jnp.ones((n, 64))
    w = jnp.full((n,), 1.0 / n)
    up, wp = pad_cohort(u, w)
    nb = bucket_clients(n)
    assert nb % TILE_N == 0 and nb >= n
    assert up.shape == (nb, 64) and wp.shape == (nb,)
    np.testing.assert_allclose(float(wp.sum()), 1.0, rtol=1e-6)
    if nb > n:                      # padded rows are zero-weight zero rows
        assert float(jnp.abs(up[n:]).sum()) == 0.0
        assert float(jnp.abs(wp[n:]).sum()) == 0.0


def test_cohort_sizes_in_one_bucket_share_padded_shape():
    """97 vs 100 clients must land on the same padded shape (no recompile)."""
    for n in (65, 97, 100, 128):
        assert bucket_clients(n) == 128


def test_kernel_weighted_identity():
    u = jnp.stack([jnp.full((100,), 3.0), jnp.full((100,), 5.0)])
    out = ops.fedavg_aggregate(u, jnp.array([0.25, 0.75]))
    np.testing.assert_allclose(np.asarray(out), 4.5, rtol=1e-6)


def test_kernel_small_tiles_multi_chunk_grid():
    """Force a multi-chunk, multi-tile grid with small tiles."""
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (37, 700))
    w = jax.nn.softmax(jax.random.normal(key, (37,)))
    out = ops.fedavg_aggregate(u, w)  # defaults
    from repro.kernels.fedavg_agg import fedavg_aggregate
    small = fedavg_aggregate(u, w, interpret=True, tile_d=256, tile_n=8)
    exp = ref.fedavg_ref(u, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(small), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


def test_kernel_aggregation_matches_einsum_oracle_on_pytrees():
    from repro.core.aggregation import fedavg_weights, weighted_average
    rng = np.random.RandomState(3)
    updates = [{"w": rng.randn(33, 17).astype(np.float32),
                "b": rng.randn(50).astype(np.float32)} for _ in range(7)]
    w = fedavg_weights([3, 5, 2, 9, 1, 4, 6])
    oracle = weighted_average(updates, w)
    kern = weighted_average(updates, w, use_kernel=True)
    for a, b in zip(jax.tree_util.tree_leaves(oracle),
                    jax.tree_util.tree_leaves(kern)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# interpret mode follows the platform (kernels/__init__.py)
# ---------------------------------------------------------------------------


def test_cpu_backend_resolves_to_interpret_mode():
    from repro.kernels import resolve_interpret
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True          # no TPU: interpreter
    assert resolve_interpret(False) is False    # an explicit bool wins
    assert resolve_interpret(True) is True
    # the default-argument kernel path runs (interpreted) on CPU and
    # matches the explicitly interpreted call bit for bit
    u = jnp.asarray(np.random.RandomState(0).randn(8, 300), jnp.float32)
    w = jnp.full((8,), 1.0 / 8, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.fedavg_aggregate(u, w)),
        np.asarray(ops.fedavg_aggregate(u, w, interpret=True)))


# ---------------------------------------------------------------------------
# batched engine vs sequential runtime
# ---------------------------------------------------------------------------


def _run(execution, client_over=None, client_cls=None, data_over=None):
    easyfl.reset()
    easyfl.init({
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 12, "batch_size": 32, **(data_over or {})},
        "server": {"rounds": 3, "clients_per_round": 5},
        "client": {"local_epochs": 2, "lr": 0.1, **(client_over or {})},
        "resources": {"execution": execution},
    })
    if client_cls is not None:
        easyfl.register_client(client_cls)
    res = easyfl.run()
    easyfl.reset()
    return res


def _assert_equivalent(rs, rb):
    for a, b in zip(jax.tree_util.tree_leaves(rs["params"]),
                    jax.tree_util.tree_leaves(rb["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        [h["train_loss"] for h in rs["history"]],
        [h["train_loss"] for h in rb["history"]], rtol=1e-4)
    np.testing.assert_allclose(
        [h["accuracy"] for h in rs["history"]],
        [h["accuracy"] for h in rb["history"]], atol=1e-5)


def test_batched_equals_sequential_fedavg():
    _assert_equivalent(_run("sequential"), _run("batched"))


def test_batched_equals_sequential_fedprox():
    over = {"proximal_mu": 0.01}
    _assert_equivalent(_run("sequential", over), _run("batched", over))


def test_batched_equals_sequential_stc():
    from repro.core.strategies.stc import STCClient
    over = {"compression": "stc", "stc_sparsity": 0.05}
    _assert_equivalent(_run("sequential", over, STCClient),
                       _run("batched", over, STCClient))


def test_batched_equals_sequential_grad_clip():
    over = {"max_grad_norm": 1.0}
    _assert_equivalent(_run("sequential", over), _run("batched", over))


def test_batched_equals_sequential_unbalanced_cohort():
    """Clients with different sample/step counts exercise the step-masking
    (padded-step freeze) path."""
    data = {"unbalanced": True, "unbalanced_sigma": 1.5}
    _assert_equivalent(_run("sequential", data_over=data),
                       _run("batched", data_over=data))


def test_batched_round_metrics_complete():
    res = _run("batched")
    h = res["history"][0]
    for key in ("round_time", "wall_time", "clients", "comm_up_bytes",
                "train_loss"):
        assert key in h
    assert h["clients"] == 5
    assert h["round_time"] > 0      # virtual clock still populated


def test_batched_rejects_mixed_batch_sizes():
    from repro.core.batched import BatchedExecutor
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData
    from repro.models.small import linear_model

    model = linear_model()
    rng = np.random.RandomState(0)
    data = ClientData(rng.randn(40, 64).astype(np.float32),
                      rng.randint(0, 10, 40).astype(np.int32))
    c1 = Client("a", model, data, ClientConfig(), batch_size=16)
    c2 = Client("b", model, data, ClientConfig(), batch_size=32)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="uniform batch size"):
        BatchedExecutor(model, max_clients=64).run_cohort([c1, c2], params, 0)


def test_batched_rejects_train_stage_override():
    from repro.core.client import Client

    class TrainOverride(Client):
        def train(self, params, round_id):
            return super().train(params, round_id)

    easyfl.reset()
    easyfl.init({
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 4, "batch_size": 32},
        "server": {"rounds": 1, "clients_per_round": 2},
        "client": {"local_epochs": 1},
        "resources": {"execution": "batched"},
    })
    easyfl.register_client(TrainOverride)
    with pytest.raises(ValueError, match="train"):
        easyfl.run()
    easyfl.reset()


def test_batched_per_client_lr_matches_sequential():
    """Non-uniform learning rates across the cohort: the batched engine
    scales each client's update by lr_i/lr_0 (exact — lr is a final linear
    factor in both optimizer families); must match per-client sequential
    training."""
    import dataclasses
    from repro.core.batched import BatchedExecutor
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData
    from repro.models.small import linear_model

    model = linear_model()
    rng = np.random.RandomState(0)
    lrs = [0.1, 0.02, 0.3, 0.1]
    clients = []
    for i, lr in enumerate(lrs):
        data = ClientData(rng.randn(48, 64).astype(np.float32),
                          rng.randint(0, 10, 48).astype(np.int32))
        cfg = dataclasses.replace(ClientConfig(local_epochs=2), lr=lr)
        clients.append(Client(f"c{i}", model, data, cfg, batch_size=16))
    params = model.init(jax.random.PRNGKey(0))

    batched = BatchedExecutor(model, max_clients=64).run_cohort(clients, params, round_id=1)
    for c, res in zip(clients, batched):
        seq = c.train(params, round_id=1)
        for a, b in zip(jax.tree_util.tree_leaves(seq["update"]),
                        jax.tree_util.tree_leaves(res["update"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(res["metrics"]["loss"],
                                   seq["metrics"]["loss"], rtol=1e-4)


def _hetero_clients(model, cfgs, n_samples=48, batch_size=16, seed=0):
    from repro.core.client import Client
    from repro.data.fed_data import ClientData

    rng = np.random.RandomState(seed)
    clients = []
    for i, cfg in enumerate(cfgs):
        data = ClientData(rng.randn(n_samples, 64).astype(np.float32),
                          rng.randint(0, 10, n_samples).astype(np.int32))
        clients.append(Client(f"c{i}", model, data, cfg,
                              batch_size=batch_size))
    return clients


def _assert_batched_matches_per_client_sequential(clients, rounds=2):
    from repro.core.batched import BatchedExecutor

    model = clients[0].model
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    for r in range(rounds):
        batched = ex.run_cohort(clients, params, round_id=r)
        for c, res in zip(clients, batched):
            seq = c.train(params, round_id=r)
            for a, b in zip(jax.tree_util.tree_leaves(seq["update"]),
                            jax.tree_util.tree_leaves(res["update"])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(res["metrics"]["loss"],
                                       seq["metrics"]["loss"], rtol=1e-4)


def test_batched_per_client_sgd_hyperparams_match_sequential():
    """Heterogeneous momentum / weight decay / nesterov (and lr) across one
    SGD cohort: the traced-hyperparam cohort program must match per-client
    sequential execution to tight tolerance."""
    from repro.core.config import ClientConfig
    from repro.models.small import linear_model

    cfgs = [
        ClientConfig(local_epochs=2, lr=0.1, momentum=0.9),
        ClientConfig(local_epochs=2, lr=0.02, momentum=0.0),
        ClientConfig(local_epochs=2, lr=0.3, momentum=0.5,
                     weight_decay=0.01),
        ClientConfig(local_epochs=2, lr=0.1, momentum=0.9, nesterov=True),
        ClientConfig(local_epochs=2, lr=0.05, momentum=0.7,
                     weight_decay=0.001, nesterov=True),
    ]
    model = linear_model()
    _assert_batched_matches_per_client_sequential(
        _hetero_clients(model, cfgs))


def test_batched_per_client_adamw_hyperparams_match_sequential():
    """Heterogeneous AdamW betas / eps / weight decay (and lr)."""
    from repro.core.config import ClientConfig
    from repro.models.small import linear_model

    cfgs = [
        ClientConfig(local_epochs=2, optimizer="adamw", lr=0.01),
        ClientConfig(local_epochs=2, optimizer="adamw", lr=0.003,
                     adam_b1=0.8, adam_b2=0.99),
        ClientConfig(local_epochs=2, optimizer="adamw", lr=0.01,
                     adam_eps=1e-6, weight_decay=0.01),
        ClientConfig(local_epochs=2, optimizer="adamw", lr=0.02,
                     adam_b1=0.95, weight_decay=0.001),
    ]
    model = linear_model()
    _assert_batched_matches_per_client_sequential(
        _hetero_clients(model, cfgs))


def test_hetero_hyperparams_zero_recompiles_across_rounds():
    """A heterogeneous cohort at fixed bucket shapes must compile exactly
    once: per-client hyperparams are traced (N,) vectors, never baked-in
    constants, so round-over-round values changes cannot retrace."""
    from repro.core.batched import BatchedExecutor, cohort_trace_count
    from repro.core.config import ClientConfig
    from repro.models.small import linear_model

    cfgs = [ClientConfig(local_epochs=2, lr=0.1 * (i + 1) / 5,
                         momentum=(0.0, 0.5, 0.9)[i % 3],
                         weight_decay=(0.0, 0.01)[i % 2],
                         nesterov=bool(i % 2))
            for i in range(5)]
    model = linear_model()
    clients = _hetero_clients(model, cfgs)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    ex.run_cohort(clients, params, round_id=0)      # warm-up trace
    before = cohort_trace_count()
    for r in range(1, 4):
        ex.run_cohort(clients, params, round_id=r)
    assert cohort_trace_count() == before, (
        "per-client hyperparam heterogeneity must not retrace the cohort "
        "program at fixed bucket shapes")


def test_batched_rejects_hand_assigned_per_client_optimizers():
    """Distinct optimizer objects not derived from the client configs
    cannot be vectorized (a cohort-uniform shared instance still can)."""
    from repro.core.batched import BatchedExecutor
    from repro.core.config import ClientConfig
    from repro.models.small import linear_model
    from repro.optim import sgd

    model = linear_model()
    clients = _hetero_clients(
        model, [ClientConfig(local_epochs=1), ClientConfig(local_epochs=1)])
    clients[0].optimizer = sgd(0.123)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="hand-assigned"):
        BatchedExecutor(model, max_clients=64).run_cohort(clients, params, 0)
    # uniform hand-built instance: allowed via the traced wrapper
    shared = sgd(0.05, momentum=0.9)
    for c in clients:
        c.optimizer = shared
    res = BatchedExecutor(model, max_clients=64).run_cohort(clients, params, 0)
    assert len(res) == 2


def test_batched_rejects_mixed_optimizer_family_naming_clients():
    """Per-client hyperparameters within one family are vectorized; only
    mixed optimizer *families* cannot share a program — the error must
    name the offending clients."""
    from repro.core.batched import BatchedExecutor
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData
    from repro.models.small import linear_model

    model = linear_model()
    rng = np.random.RandomState(0)
    data = ClientData(rng.randn(32, 64).astype(np.float32),
                      rng.randint(0, 10, 32).astype(np.int32))
    c1 = Client("sgd_a", model, data, ClientConfig(), batch_size=16)
    c2 = Client("sgd_b", model, data, ClientConfig(momentum=0.0),
                batch_size=16)
    c3 = Client("adam_c", model, data, ClientConfig(optimizer="adamw"),
                batch_size=16)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError,
                       match=r"mix optimizer families.*adam_c.*sgd_a"):
        BatchedExecutor(model, max_clients=64).run_cohort([c1, c2, c3], params, 0)
    # mixed momentum within one family no longer raises
    results = BatchedExecutor(model, max_clients=64).run_cohort([c1, c2], params, 0)
    assert len(results) == 2


def test_bad_execution_value_rejected():
    easyfl.reset()
    easyfl.init({"model": "linear", "dataset": "synthetic",
                 "resources": {"execution": "bacthed"}})
    with pytest.raises(ValueError, match="unknown execution"):
        easyfl.run()
    easyfl.reset()


def test_bucketing_pads_uneven_cohorts():
    from repro.core.batched import bucket_pow2
    assert bucket_pow2(1) == 1
    assert bucket_pow2(5) == 8
    assert bucket_pow2(8) == 8
    assert bucket_pow2(100) == 128


# ---------------------------------------------------------------------------
# the local-training loop stops at the cohort's longest client
# ---------------------------------------------------------------------------


def _clients_of_steps(steps, batch_size=16, seed=0):
    """One client per entry of ``steps``, each with a last partial batch,
    so one local epoch takes exactly that many steps; SGD with momentum,
    so a frozen client's optimizer state is exercised too."""
    from repro.core.client import Client
    from repro.core.config import ClientConfig
    from repro.data.fed_data import ClientData
    from repro.models.small import linear_model

    model = linear_model()
    rng = np.random.RandomState(seed)
    cfg = ClientConfig(local_epochs=1, lr=0.1, momentum=0.9)
    clients = []
    for i, k in enumerate(steps):
        n = batch_size * (k - 1) + 5
        data = ClientData(rng.randn(n, 64).astype(np.float32),
                          rng.randint(0, 10, n).astype(np.int32))
        clients.append(Client(f"s{seed}_{i}", model, data, cfg,
                              batch_size=batch_size))
    return clients


def _sequential(clients, params, round_id):
    return [c.train(params, round_id=round_id) for c in clients]


def _run_path(ex, path, clients, params, round_id):
    """Loss and accuracy per client, plus the per-client updates (staged)
    or the new global params (fused)."""
    if path == "staged":
        res = ex.run_cohort(clients, params, round_id=round_id)
        return ([r["metrics"]["loss"] for r in res],
                [r["metrics"]["accuracy"] for r in res],
                [r["update"] for r in res])
    # the fused program donates the params it is given: hand it a copy
    st, new_global, _ = ex.run_round_fused(
        clients, jax.tree_util.tree_map(jnp.copy, params), round_id)
    n = len(clients)
    return list(st["loss"][:n]), list(st["acc"][:n]), new_global


@pytest.mark.parametrize("path", ["staged", "fused"])
@pytest.mark.parametrize("steps", [(5, 5, 5), (3, 7)],
                         ids=["below-bucket", "unbalanced"])
def test_loop_to_longest_client_equals_sequential(path, steps):
    """Real steps below the bucket S = 8 (5 of 8), and an unbalanced cohort
    (3 and 7 of 8): the loop stops at the longest client, the shorter one
    is masked below it, and each client matches ``Client.train``."""
    from repro.core.aggregation import fedavg_weights
    from repro.core.batched import BatchedExecutor

    clients = _clients_of_steps(steps)
    model = clients[0].model
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    for r in range(2):
        seq = _sequential(clients, params, r)
        assert [s["metrics"]["batches"] for s in seq] == list(steps)
        loss, acc, out = _run_path(ex, path, clients, params, r)
        np.testing.assert_allclose(loss, [s["metrics"]["loss"] for s in seq],
                                   rtol=1e-4)
        np.testing.assert_allclose(
            acc, [s["metrics"]["accuracy"] for s in seq], atol=1e-5)
        if path == "staged":
            pairs = [(jax.tree_util.tree_leaves(s["update"]),
                      jax.tree_util.tree_leaves(u)) for s, u in zip(seq, out)]
        else:
            w = fedavg_weights([s["num_samples"] for s in seq])
            want = jax.tree_util.tree_map(
                lambda p, *us: np.asarray(p) + sum(
                    wi * np.asarray(u) for wi, u in zip(w, us)),
                params, *[s["update"] for s in seq])
            pairs = [(jax.tree_util.tree_leaves(want),
                      jax.tree_util.tree_leaves(out))]
        for a_leaves, b_leaves in pairs:
            for a, b in zip(a_leaves, b_leaves):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)
        if path == "fused":
            params = out


@pytest.mark.parametrize("path", ["staged", "fused"])
def test_skipped_step_count_grows_by_bucket_less_longest_client(path):
    from repro.core.batched import (BatchedExecutor, bucket_pow2,
                                    skipped_step_count)

    model = _clients_of_steps([1])[0].model
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    for steps in [(5, 5, 5), (3, 7), (8, 2), (4,)]:
        before = skipped_step_count()
        _run_path(ex, path, _clients_of_steps(steps, seed=sum(steps)),
                  params, 0)
        assert skipped_step_count() - before == \
            bucket_pow2(max(steps)) - max(steps), steps


@pytest.mark.parametrize("path", ["staged", "fused"])
def test_longest_client_changing_within_a_bucket_does_not_recompile(path):
    """Two rounds at S = 8 whose longest clients take 5, then 7 steps: the
    trip count is traced, so neither program traces again."""
    from repro.core.batched import (BatchedExecutor, cohort_trace_count,
                                    round_trace_count)

    model = _clients_of_steps([1])[0].model
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedExecutor(model, max_clients=64)
    _run_path(ex, path, _clients_of_steps((5, 5, 5), seed=1), params, 0)
    before = (cohort_trace_count(), round_trace_count())
    _run_path(ex, path, _clients_of_steps((7, 5, 3), seed=2), params, 1)
    assert (cohort_trace_count(), round_trace_count()) == before


@pytest.mark.parametrize("path", ["staged", "fused"])
@pytest.mark.parametrize("steps", [(5, 5, 5), (3, 7), (8, 2)],
                         ids=["below-bucket", "unbalanced", "full-bucket"])
def test_loop_runs_the_longest_clients_steps_not_the_bucket(path, steps):
    """The model's forward pass runs once per loop iteration (a host
    callback counts them): ``max(n_steps)`` times, not the bucket's 8."""
    import dataclasses

    from repro.core.batched import BatchedExecutor

    calls = []
    clients = _clients_of_steps(steps)
    base = clients[0].model

    def apply(params, x):
        jax.debug.callback(lambda: calls.append(1))
        return base.apply(params, x)

    model = dataclasses.replace(base, apply=apply)
    for c in clients:
        c.model = model
    ex = BatchedExecutor(model, max_clients=64)
    _run_path(ex, path, clients, model.init(jax.random.PRNGKey(0)), 0)
    jax.effects_barrier()
    assert len(calls) == max(steps)
