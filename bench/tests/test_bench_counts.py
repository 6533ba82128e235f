"""The yardstick's counts and references against the system, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as refm
import spec
import traffic as tr
from repro.models.registry import get_model

CONFIGS = ["resnet18_cifar10", "femnist_cnn_leaf"]


@pytest.fixture(scope="module")
def bench():
    return spec.Bench()


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_flops_match_xla_cost_analysis(bench, name):
    """The analytic count is of convolutions and dense layers only, so it
    sits just under XLA's count, which adds the elementwise work (biases,
    normalisation, activations, pooling): within 2%, and never above."""
    cfg = bench.config(name)
    model = get_model(cfg["program_model"])
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1,) + tuple(cfg["input_shape"]), jnp.float32)
    ca = jax.jit(model.apply).lower(params, x).compile().cost_analysis()
    xla = (ca[0] if isinstance(ca, list) else ca)["flops"]
    ratio = bench.count(name).forward_flops(cfg) / xla
    assert 0.98 <= ratio <= 1.0, ratio


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_has_the_systems_parameters(bench, name):
    cfg = bench.config(name)
    model = get_model(cfg["program_model"])
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(lambda k: bench.reference(name).init(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(have)
    assert [a.shape for a in jax.tree_util.tree_leaves(want)] == \
        [a.shape for a in jax.tree_util.tree_leaves(have)]
    assert sum(a.size for a in jax.tree_util.tree_leaves(have)) == cfg["params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_forward_matches_the_system(bench, name):
    """Same weights, same inputs, float32 on the CPU: the plain reference
    and the system's model agree to rounding."""
    cfg = bench.config(name)
    model = get_model(cfg["program_model"])
    params = refm.init_weights(bench.reference(name), cfg, seed=7)
    fed = tr.Federation({"population": 2, "samples_per_client": 3, "clients_per_round": 1,
                         "batch_size": 3, "local_epochs": 1,
                         "data": {"noise": 1.0, "client_shift": 0.5}}, cfg, seed=7)
    x, _ = fed.data(1)
    want = bench.reference(name).forward(params, jnp.asarray(x), cfg)
    got = model.apply(params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_kernel_byte_counts(bench):
    stc, fed = bench.count("stc_topk"), bench.count("fedavg_agg")
    # leaves under 64 elements stay dense and are not compressed
    assert stc.bytes_per_round(16, [1000, 63, 8192], 0.01) == 16 * 9192 * 4 + 16 * 9192 * 0.08
    assert fed.bytes_per_round(16, 1000) == 4 * 16 * 1000 + 4 * 16 + 4 * 1000
    assert fed.flops_per_round(16, 1000) == 2 * 16 * 1000
    d = 11_178_762
    assert fed.bytes_per_round(16, d) / 819e9 == pytest.approx(9.28e-4, rel=0.01)


def test_reference_stc_keeps_about_the_share_asked():
    x = jax.random.normal(jax.random.PRNGKey(0), (3 * refm.STC_TILE + 100,))
    out = refm.stc_leaf(x, 0.01)
    kept = int(jnp.sum(out != 0))
    assert abs(kept - 0.01 * x.size) <= 4
    vals = np.unique(np.abs(np.asarray(out)[np.asarray(out) != 0]))
    assert len(vals) == 4            # one magnitude per tile
