"""Whole-round fusion (``resources.round_fusion``): parity, structure,
resume, and the satellite fixes that ride along (dense payload dtype
accounting, ``server.server_lr`` plumbing, ``tracking.round_sync``).

The fused path must be *indistinguishable* from the staged fast path in
results — bit-identical for ``none``/``stc`` compression, <= 1e-6 for
``int8`` (one fused program gives XLA more fusion freedom) — while
executing as ONE dispatch with ONE batched host fetch per round and zero
retraces across rounds.  The 8-device mesh leg runs in a subprocess that
owns ``--xla_force_host_platform_device_count`` (conftest asserts it is
never set globally).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest

import repro as easyfl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(fusion, extra=None, execution="batched", rounds=3):
    easyfl.reset()
    cfg = {
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 10, "batch_size": 32},
        "server": {"rounds": rounds, "clients_per_round": 5},
        "client": {"local_epochs": 2, "lr": 0.1},
        "resources": {"execution": execution, "round_fusion": fusion},
    }
    for k, v in (extra or {}).items():
        cfg.setdefault(k, {}).update(v)
    easyfl.init(cfg)
    res = easyfl.run()
    easyfl.reset()
    return res


def _assert_params(a, b, atol=0.0):
    for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                    jax.tree_util.tree_leaves(b["params"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=atol)


FAULTS = {"faults": {"dropout_prob": 0.25, "crash_prob": 0.15,
                     "nan_update_prob": 0.25, "max_update_norm": 100.0,
                     "seed": 7}}


# ---------------------------------------------------------------------------
# parity matrix: {none, stc, int8} x {faults on/off} x {flat, hierarchical}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", ["none", "stc", "int8"])
@pytest.mark.parametrize("faults", [False, True])
@pytest.mark.parametrize("topology", ["flat", "hierarchical"])
def test_fused_matches_staged(comp, faults, topology):
    extra = {"client": {"compression": comp},
             "resources": {"aggregation_topology": topology}}
    if faults:
        extra.update(FAULTS)
    fused = _run("auto", extra)
    staged = _run("off", extra)
    # int8: the fused program is one XLA computation, so reassociation
    # may differ by one float32 ulp; none/stc replicate bit for bit
    _assert_params(fused, staged, atol=1e-6 if comp == "int8" else 0.0)
    np.testing.assert_allclose(
        [h["train_loss"] for h in fused["history"]],
        [h["train_loss"] for h in staged["history"]], rtol=1e-6)
    np.testing.assert_allclose(
        [h["comm_up_bytes"] for h in fused["history"]],
        [h["comm_up_bytes"] for h in staged["history"]])
    if faults:
        for key in ("survivors", "dropped", "crashed", "rejected"):
            assert [h[key] for h in fused["history"]] == \
                [h[key] for h in staged["history"]]


def test_fused_matches_sequential():
    fused = _run("auto")
    seq = _run("off", execution="sequential")
    for x, y in zip(jax.tree_util.tree_leaves(fused["params"]),
                    jax.tree_util.tree_leaves(seq["params"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# structure: one trace, zero retraces, one dispatch + one fetch per round
# ---------------------------------------------------------------------------


def test_fused_one_dispatch_one_fetch_zero_retraces():
    from repro.core import batched

    easyfl.reset()
    easyfl.init({
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 10, "batch_size": 32},
        "server": {"rounds": 4, "clients_per_round": 5, "test_every": 0},
        "client": {"local_epochs": 2, "lr": 0.1},
        "resources": {"execution": "batched"},
    })
    t0 = batched.round_trace_count()
    d0, h0 = batched.dispatch_count(), batched.host_sync_count()
    easyfl.run()
    easyfl.reset()
    # one trace for the first round, zero retraces over rounds 2..4
    assert batched.round_trace_count() - t0 == 1
    assert batched.dispatch_count() - d0 == 4      # 1 per round
    assert batched.host_sync_count() - h0 == 4     # 1 batched fetch per round


# ---------------------------------------------------------------------------
# fallback is loud, "off" is honored, bad values refused
# ---------------------------------------------------------------------------


def test_ineligible_round_warns_once_and_falls_back():
    from repro.core.server import Server

    class CustomApply(Server):
        def apply_delta(self, delta, server_lr=None):
            super().apply_delta(delta, server_lr)

    easyfl.reset()
    easyfl.init({
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": 6, "batch_size": 32},
        "server": {"rounds": 2, "clients_per_round": 4},
        "client": {"local_epochs": 1, "lr": 0.1},
        "resources": {"execution": "batched"},
    })
    easyfl.register_server(CustomApply)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        easyfl.run()
    easyfl.reset()
    hits = [w for w in caught
            if "round_fusion" in str(w.message)]
    assert len(hits) == 1                      # once per trainer, not per round
    assert "apply_delta override" in str(hits[0].message)


def test_round_fusion_off_uses_staged_path():
    from repro.core import batched

    t0 = batched.round_trace_count()
    _run("off", rounds=2)
    assert batched.round_trace_count() == t0   # fused program never built


def test_bad_round_fusion_value_rejected():
    easyfl.reset()
    with pytest.raises(ValueError, match="round_fusion"):
        easyfl.init({"model": "linear", "dataset": "synthetic",
                     "resources": {"round_fusion": "sometimes"}})
        easyfl.run()
    easyfl.reset()


# ---------------------------------------------------------------------------
# satellite: server_lr plumbing (staged + fused + sequential parity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fusion", ["auto", "off"])
def test_server_lr_batched_matches_sequential(fusion):
    extra = {"server": {"server_lr": 0.5}}
    bat = _run(fusion, extra)
    seq = _run("off", extra, execution="sequential")
    for x, y in zip(jax.tree_util.tree_leaves(bat["params"]),
                    jax.tree_util.tree_leaves(seq["params"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)
    # and it actually deviates from the lr=1 run
    base = _run(fusion)
    deltas = [float(np.abs(np.asarray(x) - np.asarray(y)).max())
              for x, y in zip(jax.tree_util.tree_leaves(bat["params"]),
                              jax.tree_util.tree_leaves(base["params"]))]
    assert max(deltas) > 1e-4


def test_bad_server_lr_rejected():
    easyfl.reset()
    with pytest.raises(ValueError, match="server_lr"):
        easyfl.init({"model": "linear", "dataset": "synthetic",
                     "server": {"server_lr": 0.0}})
        easyfl.run()
    easyfl.reset()


# ---------------------------------------------------------------------------
# satellite: dense payload bytes use real dtype itemsize
# ---------------------------------------------------------------------------


def test_dense_update_bytes_uses_leaf_dtype():
    import jax.numpy as jnp

    from repro.core.rounds import dense_update_bytes

    tree = {"w": jnp.zeros((8, 4), jnp.float32),        # 32 * 4
            "h": jnp.zeros((10,), jnp.bfloat16),        # 10 * 2
            "q": jnp.zeros((6,), jnp.int8),             # 6 * 1
            "b": np.zeros((3,), np.float16)}            # 3 * 2
    assert dense_update_bytes(tree) == 32 * 4 + 10 * 2 + 6 * 1 + 3 * 2


def test_dense_round_reports_dtype_true_wire_bytes():
    res = _run("auto", rounds=1)
    # linear(64, 10): one (64, 10) f32 matrix + (10,) f32 bias per client
    per_client = (64 * 10 + 10) * 4
    assert res["history"][0]["comm_up_bytes"] == per_client * 5


# ---------------------------------------------------------------------------
# satellite: tracking.round_sync deferred finalize
# ---------------------------------------------------------------------------


def test_round_sync_false_matches_sync_run():
    extra = {"tracking": {"round_sync": False}, "server": {"test_every": 2}}
    deferred = _run("auto", extra, rounds=4)
    synced = _run("auto", {"server": {"test_every": 2}}, rounds=4)
    _assert_params(deferred, synced)
    assert len(deferred["history"]) == 4
    assert [sorted(h) for h in deferred["history"]] == \
        [sorted(h) for h in synced["history"]]
    np.testing.assert_allclose(
        [h["train_loss"] for h in deferred["history"]],
        [h["train_loss"] for h in synced["history"]])


@pytest.mark.parametrize("bad", [
    {"faults": {"dropout_prob": 0.5}},
    {"resources": {"round_deadline": 5.0}},
])
def test_round_sync_false_rejects_exact_clock_consumers(bad):
    easyfl.reset()
    with pytest.raises(ValueError, match="round_sync"):
        easyfl.init({"model": "linear", "dataset": "synthetic",
                     "tracking": {"round_sync": False}, **bad})
        easyfl.run()
    easyfl.reset()


# ---------------------------------------------------------------------------
# kill-and-resume bit-identity with fusion on (compressed EF state rides
# the same tiered store as the staged path)
# ---------------------------------------------------------------------------


def test_kill_and_resume_bit_identical_with_fusion(tmp_path):
    from repro.core.config import Config
    from repro.core.rounds import Trainer
    from repro.core.server import Server
    from repro.data.fed_data import build_federated_data
    from repro.models.registry import get_model

    def make(d):
        cfg = Config.make({
            "model": "linear",
            "data": {"dataset": "synthetic", "num_clients": 8,
                     "batch_size": 32},
            "server": {"rounds": 4, "clients_per_round": 4},
            "client": {"local_epochs": 1, "lr": 0.1, "compression": "stc"},
            "resources": {"execution": "batched", "round_fusion": "auto"},
            "checkpoint": {"every": 2, "dir": d},
            "tracking": {"enabled": False},
        })
        model = get_model(cfg.model)
        fed = build_federated_data(cfg.data)
        t = Trainer(cfg, model, fed, server=Server(model, cfg, fed.test))
        return t, model

    da, db = str(tmp_path / "A"), str(tmp_path / "B")
    ta, model = make(da)
    ta.server.params = model.init(jax.random.PRNGKey(ta.cfg.seed))
    ra = ta.run()

    tb, model = make(db)
    tb.server.params = model.init(jax.random.PRNGKey(tb.cfg.seed))
    for r in range(2):                          # ... killed after round 2
        tb.run_round(r)
        tb._maybe_checkpoint(r + 1)
    tc, _ = make(db)
    rc = tc.resume()

    _assert_params(ra, rc)
    assert len(rc["history"]) == 4


# ---------------------------------------------------------------------------
# 8-device mesh parity (subprocess owns the forced device count)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fused_matches_staged_on_8_device_mesh():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        assert len(jax.devices()) == 8
        import repro as easyfl

        def run(fusion, comp):
            easyfl.reset()
            easyfl.init({
                "model": "linear", "dataset": "synthetic",
                "data": {"num_clients": 12, "batch_size": 32},
                "server": {"rounds": 2, "clients_per_round": 8},
                "client": {"local_epochs": 1, "lr": 0.1,
                           "compression": comp},
                "resources": {"execution": "batched",
                              "round_fusion": fusion,
                              "distributed": "data"},
            })
            res = easyfl.run()
            easyfl.reset()
            return res

        for comp in ("none", "stc"):
            f, s = run("auto", comp), run("off", comp)
            for x, y in zip(jax.tree_util.tree_leaves(f["params"]),
                            jax.tree_util.tree_leaves(s["params"])):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=0, atol=1e-6)
        print("MESH_FUSED_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_FUSED_OK" in r.stdout


@pytest.mark.slow
def test_fused_round_traces_once_with_stc_on_mesh():
    """Over rounds that keep bringing in new clients, the sharded fused STC
    round compiles once: the error-feedback store is sized up front to
    the clients the run can touch, and the first round's params are
    placed on the mesh the way the program returns them."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import repro as easyfl
        from repro.core import batched

        for distributed in ("none", "data"):
            easyfl.reset()
            easyfl.init({
                "model": "linear", "dataset": "synthetic",
                "data": {"num_clients": 100, "batch_size": 32},
                "server": {"rounds": 3, "clients_per_round": 16,
                           "test_every": 0},
                "client": {"local_epochs": 1, "compression": "stc"},
                "resources": {"execution": "batched",
                              "distributed": distributed},
            })
            t0 = batched.round_trace_count()
            easyfl.run()
            easyfl.reset()
            assert batched.round_trace_count() - t0 == 1, distributed
        print("TRACED_ONCE")
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRACED_ONCE" in r.stdout
