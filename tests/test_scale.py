"""Million-client population machinery (docs/scale.md).

* TieredRowStore: LRU eviction order, host-spill -> bit-identical reload,
  cohort assembly spanning hot / spilled / never-seen clients, bounded
  device tier, tier-agnostic state round-trips.
* Hierarchical streaming aggregation: 1e-6 equality with flat FedAvg at
  every fanout, bit-equality when fanout >= cohort, composition with
  staleness weights, end-to-end topology parity.
* Virtual populations: O(k) id-space sampling, deterministic shard
  regeneration, auto/on/off policy, a 10^6-client round on one host.
* Checkpoint/resume with spilled EF residuals stays bit-identical.
* init() flat-key folding + register_dataset symmetry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro as easyfl
from repro.core.config import Config, validate_config
from repro.core.rounds import Trainer
from repro.core.server import Server
from repro.core.tiered_store import TieredRowStore
from repro.data.fed_data import (
    ClientIdSpace, VirtualFederatedDataset, build_federated_data,
)
from repro.data.synthetic import make_client_shard
from repro.kernels.fedavg_agg import fedavg_aggregate_tree
from repro.models.registry import get_model


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _params_equal(a, b):
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


def _row(cid, d=4):
    rng = np.random.RandomState(abs(hash(cid)) % (2**31))
    return [rng.randn(d).astype(np.float32),
            rng.randn(d, 2).astype(np.float32)]


# ---------------------------------------------------------------------------
# TieredRowStore
# ---------------------------------------------------------------------------


def test_store_lru_eviction_order():
    st = TieredRowStore(3, spill="drop")
    st.ensure(["a", "b", "c"], _row)
    st.ensure(["a"], _row)                 # refresh a: LRU order b, c, a
    st.ensure(["d"], _row)                 # evicts b (least recent)
    assert set(st.rows) == {"a", "c", "d"}
    st.ensure(["e"], _row)                 # evicts c
    assert set(st.rows) == {"a", "d", "e"}
    assert st.stats["evictions"] == 2


def test_store_host_spill_reloads_bit_identically():
    st = TieredRowStore(2, spill="host")
    first = [np.array(l[0]) for l in st.gather(["a"], _row)]
    st.ensure(["b", "c"], _row)            # a spilled to host
    assert "a" not in st.rows and "a" in st
    assert list(st.spilled_ids()) == ["a"]
    again = [np.array(l[0]) for l in st.gather(["a"], _row)]
    for x, y in zip(first, again):
        np.testing.assert_array_equal(x, y)
    assert st.stats["reloads"] == 1 and st.stats["recomputes"] == 3


def test_store_cohort_spans_hot_spilled_and_never_seen():
    st = TieredRowStore(4, spill="host")
    st.ensure(["a", "b"], _row)            # hot
    st.ensure(["c", "d", "e", "f"], _row)  # spills a, b
    made = []
    leaves = st.gather(["e", "a", "zz"],   # hot + spilled + never-seen
                       lambda cid: made.append(cid) or _row(cid))
    assert made == ["zz"]                  # only the cold client recomputes
    for li, leaf in enumerate(leaves):
        np.testing.assert_array_equal(np.array(leaf[0]), _row("e")[li])
        np.testing.assert_array_equal(np.array(leaf[1]), _row("a")[li])
        np.testing.assert_array_equal(np.array(leaf[2]), _row("zz")[li])


def test_store_device_tier_is_bounded_but_pins_cohort():
    st = TieredRowStore(4, spill="drop")
    for i in range(20):
        st.ensure([f"c{i}"], _row)
    assert len(st.rows) <= 4 and st.alloc <= 4
    # a cohort larger than capacity pins the tier open for the round
    big = [f"big{i}" for i in range(7)]
    st.ensure(big, _row)
    assert set(big) <= set(st.rows)
    bytes_before = st.device_bytes()
    for i in range(10):
        st.ensure([f"later{i}"], _row)
    assert st.device_bytes() <= bytes_before    # never grows past max seen


def test_store_state_roundtrip_is_tier_agnostic():
    st = TieredRowStore(2, spill="host")
    ids = [f"c{i}" for i in range(6)]
    for cid in ids:
        st.ensure([cid], _row)             # most spilled, some hot
    snap = st.state()
    assert set(snap["clients"]) == set(ids)
    st2 = TieredRowStore(3, spill="host")  # different device-tier size
    st2.load_state(snap)
    for cid in ids:
        got = [np.array(l[0]) for l in st2.gather([cid], _row)]
        for x, y in zip(got, _row(cid)):
            np.testing.assert_array_equal(x, y)


def test_set_rows_matches_scatter_and_drops_out_of_range():
    from repro.core.tiered_store import set_rows

    rng = np.random.RandomState(0)
    m = rng.randn(6, 3, 2).astype(np.float32)
    vals = rng.randn(4, 3, 2).astype(np.float32)
    rows = np.array([4, 0, 6, 2], np.int32)        # 6 is out of range
    want = m.copy()
    want[[4, 0, 2]] = vals[[0, 1, 3]]
    np.testing.assert_array_equal(np.asarray(set_rows(m, rows, vals)), want)


def test_store_rejects_bad_args():
    with pytest.raises(ValueError, match="spill"):
        TieredRowStore(4, spill="nope")
    with pytest.raises(ValueError, match="capacity"):
        TieredRowStore(0)


# ---------------------------------------------------------------------------
# hierarchical streaming aggregation
# ---------------------------------------------------------------------------


def _updates(n=100, d=257, seed=0):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = rng.rand(n).astype(np.float64)
    return u, jnp.asarray((w / w.sum()).astype(np.float32))


@pytest.mark.parametrize("fanout", [0, 2, 5, 16])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_tree_matches_flat_within_tolerance(fanout, use_kernel):
    u, w = _updates()
    flat = np.asarray(jnp.einsum("n,nd->d", w, u))
    tree = np.asarray(fedavg_aggregate_tree(
        u, w, fanout=fanout, use_kernel=use_kernel, interpret=True))
    np.testing.assert_allclose(tree, flat, atol=1e-6)


def test_tree_bit_equal_to_flat_when_fanout_covers_cohort():
    u, w = _updates(n=40)
    for fanout in (40, 64, 1000):
        tree = np.asarray(fedavg_aggregate_tree(
            u, w, fanout=fanout, use_kernel=False))
        np.testing.assert_array_equal(
            tree, np.asarray(jnp.einsum("n,nd->d", w, u)))


def test_tree_composes_with_staleness_weights():
    from repro.kernels.fedavg_agg import fold_staleness
    u, w = _updates(n=30)
    s = jnp.asarray(np.random.RandomState(1).randint(0, 5, 30), jnp.float32)
    folded = fold_staleness(w, s, 0.5)
    flat = np.asarray(jnp.einsum("n,nd->d", folded, u))
    tree = np.asarray(fedavg_aggregate_tree(
        u, w, fanout=4, use_kernel=False, staleness=s, staleness_power=0.5))
    np.testing.assert_allclose(tree, flat, atol=1e-6)


def test_invalid_topology_and_fanout_rejected_at_init():
    with pytest.raises(ValueError, match="aggregation_topology"):
        validate_config(Config.make(
            {"resources": {"aggregation_topology": "ring"}}))
    with pytest.raises(ValueError, match="aggregation_fanout"):
        validate_config(Config.make(
            {"resources": {"aggregation_fanout": 1}}))


def _topology_trainer(topology, fanout=0, execution="batched"):
    cfg = Config.make({
        "model": "linear",
        "data": {"dataset": "synthetic", "num_clients": 12,
                 "batch_size": 32},
        "server": {"rounds": 2, "clients_per_round": 6, "test_every": 0},
        "client": {"local_epochs": 1, "lr": 0.1},
        "resources": {"execution": execution,
                      "aggregation_topology": topology,
                      "aggregation_fanout": fanout},
        "tracking": {"enabled": False},
    })
    model = get_model("linear")
    fed = build_federated_data(cfg.data)
    t = Trainer(cfg, model, fed, server=Server(model, cfg, fed.test))
    t.server.params = model.init(jax.random.PRNGKey(cfg.seed))
    return t


@pytest.mark.parametrize("execution", ["batched", "sequential"])
def test_end_to_end_topology_parity(execution):
    """fanout >= cohort short-circuits to the flat program: a whole run
    under the hierarchical knob is bit-identical to flat."""
    flat = _topology_trainer("flat", execution=execution).run()
    tree = _topology_trainer("hierarchical", fanout=64,
                             execution=execution).run()
    assert _params_equal(flat["params"], tree["params"])


def test_end_to_end_hierarchical_close_to_flat():
    flat = _topology_trainer("flat").run()
    tree = _topology_trainer("hierarchical", fanout=2).run()
    for x, y in zip(_leaves(flat["params"]), _leaves(tree["params"])):
        np.testing.assert_allclose(x, y, atol=1e-5)


# ---------------------------------------------------------------------------
# virtual populations
# ---------------------------------------------------------------------------


def test_id_space_sampling_is_o_k_and_excludes():
    s = ClientIdSpace(1_000_000)
    assert len(s) == 1_000_000 and s[7] == "client_0007"
    assert "client_999999" in s and "client_1000000" not in s
    rng = np.random.RandomState(0)
    a = s.sample(rng, 100)
    b = s.sample(rng, 100, exclude=set(a))
    assert len(set(a)) == 100 and not set(a) & set(b)
    # same rng state -> same draw (selection determinism)
    c = ClientIdSpace(1_000_000).sample(np.random.RandomState(0), 100)
    assert a == c
    # small spaces fall back to a complement draw and still exclude
    tiny = ClientIdSpace(6)
    got = tiny.sample(np.random.RandomState(1), 10, exclude={"client_0002"})
    assert sorted(got) == [f"client_{i:04d}" for i in (0, 1, 3, 4, 5)]


def test_virtual_shards_regenerate_bit_identically():
    fed = VirtualFederatedDataset("synthetic", 1_000_000, seed=3)
    a, b = fed.clients["client_424242"], fed.clients["client_424242"]
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)
    x, y = make_client_shard("synthetic", 424242, 0, seed=3)
    np.testing.assert_array_equal(a.x, x)
    with pytest.raises(KeyError):
        fed.clients["client_9999999"]
    assert fed.stats()["num_clients"] == 1_000_000


def test_virtual_policy_auto_on_off():
    from repro.core.config import DataConfig
    small = build_federated_data(
        DataConfig(dataset="synthetic", num_clients=100))
    assert not isinstance(small, VirtualFederatedDataset)
    auto = build_federated_data(
        DataConfig(dataset="synthetic", num_clients=50_000))
    assert isinstance(auto, VirtualFederatedDataset)
    off = build_federated_data(
        DataConfig(dataset="synthetic", num_clients=100, virtual="off",
                   samples_per_client=0))
    assert not isinstance(off, VirtualFederatedDataset)
    forced = build_federated_data(
        DataConfig(dataset="femnist", num_clients=100, virtual="on"))
    assert isinstance(forced, VirtualFederatedDataset)
    with pytest.raises(ValueError, match="virtual"):
        build_federated_data(
            DataConfig(dataset="shakespeare", num_clients=100, virtual="on"))


def test_million_client_round_runs_on_one_host():
    easyfl.reset()
    try:
        easyfl.init({
            "model": "linear",
            "data": {"dataset": "synthetic", "num_clients": 1_000_000,
                     "batch_size": 32},
            "server": {"rounds": 2, "clients_per_round": 100,
                       "test_every": 0},
            "client": {"local_epochs": 1, "lr": 0.1},
            "resources": {"execution": "batched",
                          "aggregation_topology": "hierarchical"},
            "tracking": {"enabled": False},
        })
        res = easyfl.run()
        assert res["rounds"] == 2
        assert np.isfinite(res["final"]["train_loss"])
    finally:
        easyfl.reset()


def test_heterogeneity_is_stateless_but_honors_overrides():
    from repro.core.config import SystemHeterogeneityConfig
    from repro.simulation.heterogeneity import SystemHeterogeneity
    het = SystemHeterogeneity(SystemHeterogeneityConfig(enabled=True))
    r1 = het.speed_ratio("client_0042")
    assert het.assignment == {}            # nothing cached, O(1) memory
    assert het.speed_ratio("client_0042") == r1
    het2 = SystemHeterogeneity(SystemHeterogeneityConfig(enabled=True))
    assert het2.speed_ratio("client_0042") == r1   # process-stable
    het.assignment["client_0042"] = 99.0   # explicit override wins
    assert het.speed_ratio("client_0042") == 99.0


def test_tracking_client_history_retention():
    from repro.tracking import Tracker
    t = Tracker(client_history_rounds=2)
    for r in range(5):
        t.track_round("task", r, loss=float(r))
        t.track_client("task", r, "c0", loss=float(r))
    task = t.get_task("task")
    assert sorted(task.rounds) == [0, 1, 2, 3, 4]   # round level kept
    kept = [r for r in task.rounds if task.rounds[r].clients]
    assert kept == [3, 4]
    assert t.round_series("task", "loss") == [0.0, 1.0, 2.0, 3.0, 4.0]


# ---------------------------------------------------------------------------
# checkpoint/resume with spilled EF residuals
# ---------------------------------------------------------------------------


def test_resume_with_spilled_ef_residuals_bit_identical(tmp_path,
                                                        monkeypatch):
    """With the EF device tier capped below the touched-client count,
    residuals spill to the host mid-run; a kill-and-resume must still be
    bit-identical to the uninterrupted run."""
    from repro.core.batched import BatchedExecutor

    monkeypatch.setattr(BatchedExecutor, "EF_MAX_CLIENTS", 3)

    def make(d):
        cfg = Config.make({
            "model": "linear",
            "data": {"dataset": "synthetic", "num_clients": 10,
                     "batch_size": 32},
            "server": {"rounds": 4, "clients_per_round": 5,
                       "test_every": 0},
            "client": {"local_epochs": 1, "lr": 0.1, "compression": "stc"},
            "resources": {"execution": "batched"},
            "tracking": {"enabled": False},
            "checkpoint": {"every": 2, "dir": d},
        })
        model = get_model("linear")
        fed = build_federated_data(cfg.data)
        t = Trainer(cfg, model, fed, server=Server(model, cfg, fed.test))
        t.server.params = model.init(jax.random.PRNGKey(cfg.seed))
        return t

    ra = make(str(tmp_path / "A")).run()
    tb = make(str(tmp_path / "B"))
    assert tb.engine.EF_MAX_CLIENTS == 3
    for r in range(2):
        tb.run_round(r)
        tb._maybe_checkpoint(r + 1)
    assert len(tb.engine._ef._host) > 0    # spill actually happened
    rc = make(str(tmp_path / "B")).resume()
    assert _params_equal(ra["params"], rc["params"])


# ---------------------------------------------------------------------------
# low-code config surface
# ---------------------------------------------------------------------------


def test_init_folds_any_unambiguous_flat_key():
    easyfl.reset()
    try:
        cfg = easyfl.init({
            "dataset": "synthetic", "num_clients": 8,
            "clients_per_round": 4, "local_epochs": 1, "lora_rank": 0,
            "aggregation_topology": "hierarchical", "rounds": 2,
        })
        assert cfg.data.dataset == "synthetic"
        assert cfg.data.num_clients == 8
        assert cfg.server.clients_per_round == 4
        assert cfg.server.rounds == 2
        assert cfg.client.local_epochs == 1
        assert cfg.resources.aggregation_topology == "hierarchical"
    finally:
        easyfl.reset()


def test_init_flat_key_ambiguity_names_candidates():
    easyfl.reset()
    try:
        with pytest.raises(KeyError, match=r"server\.compression"):
            easyfl.init({"dataset": "synthetic", "compression": "stc"})
        with pytest.raises(KeyError, match=r"client\.compression"):
            easyfl.init({"dataset": "synthetic", "compression": "stc"})
        with pytest.raises(KeyError, match="conflicts"):
            easyfl.init({"dataset": "synthetic",
                         "data": {"dataset": "femnist"}})
        with pytest.raises(KeyError, match="unknown config key"):
            easyfl.init({"datsaet": "synthetic"})
    finally:
        easyfl.reset()


def test_register_dataset_requires_name_and_adopts_test():
    from repro.data.synthetic import RawDataset
    easyfl.reset()
    try:
        rng = np.random.RandomState(0)
        raw = RawDataset(rng.randn(200, 64).astype(np.float32),
                         rng.randint(0, 10, 200).astype(np.int32), 10)
        with pytest.raises(ValueError, match="name"):
            easyfl.register_dataset(raw)
        held = RawDataset(np.zeros((50, 64), np.float32),
                          np.zeros(50, np.int32), 10)
        easyfl.register_dataset(raw, test=held, name="mydata")
        easyfl.init({"dataset": "mydata", "model": "linear",
                     "data": {"num_clients": 5},
                     "server": {"rounds": 1, "clients_per_round": 2}})
        from repro.core.api import _ctx
        assert len(_ctx.fed_data.test.x) == 50       # adopted split
        assert _ctx.fed_data.stats()["total_samples"] == 200  # all trained
        assert easyfl.run()["rounds"] == 1
    finally:
        easyfl.reset()
