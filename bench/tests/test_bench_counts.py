"""The yardstick's counts and references against the system, on the CPU."""
from pathlib import Path

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


@pytest.mark.parametrize("name,traffic", [("resnet18_cifar10", "cifar10_iid_c16_stc"),
                                          ("femnist_cnn_leaf", "leaf_writers_c100")])
def test_round_mfu_falls_back_to_three_forwards(bench, name, traffic):
    """A count file without ``train_flops`` trains a sample in three
    forwards: the cells read what they read before it could give one."""
    assert not hasattr(bench.count(name), "train_flops")
    cfg = bench.config(name)
    ctx = {"bench": bench, "workload": {"config": name}, "config": cfg, "chips": 1,
           "device_kind": "TPU v5 lite", "samples_per_s": 5000.5}
    want = 100.0 * 3.0 * bench.count(name).forward_flops(cfg) * 5000.5 / 197e12
    assert bench.metric_reader("round_mfu").read(ctx) == pytest.approx(want, rel=1e-12)


TINY = Path(__file__).resolve().parent / "fixtures" / "tiny"


@pytest.fixture(scope="module")
def tiny():
    return spec.Bench(root=TINY, bench_dir=TINY)


def test_round_mfu_reads_the_configurations_train_flops(bench, tiny):
    cfg = tiny.config("tiny_lm_lora")
    count = tiny.count("tiny_lm_lora")
    ctx = {"bench": tiny, "workload": {"config": "tiny_lm_lora"}, "config": cfg, "chips": 4,
           "device_kind": "TPU v5 lite", "samples_per_s": 1e6}
    share = bench.metric_reader("round_mfu.lora").read(ctx)
    assert share == pytest.approx(100.0 * count.train_flops(cfg) * 1e6 / (4 * 197e12))
    # frozen weights compute no weight gradient: under three forwards
    assert 2 * count.forward_flops(cfg) < count.train_flops(cfg) < 3 * count.forward_flops(cfg)


def _tiny_lm(tiny, seed):
    cfg, ref, traffic = (tiny.config("tiny_lm_lora"), tiny.reference("tiny_lm_lora"),
                         tiny.traffic("tiny_lm_lora"))
    fed = tr.Federation(traffic, cfg, seed)
    return cfg, ref, traffic, fed


def test_lora_reference_matches_the_system(tiny):
    """Same base, same adapters (``B`` drawn, not zero), same tokens,
    float32 on the CPU: the reference's decoder and next-token loss agree
    with the system's ``tiny_lm`` under LoRA to rounding."""
    from repro.models.lora import lora_wrap

    cfg, ref, _, fed = _tiny_lm(tiny, seed=7)
    base = refm.init_frozen(ref, cfg, seed=7)
    adapters = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.size), a.shape) * 0.1,
        refm.init_weights(ref, cfg, seed=7))
    model = get_model(cfg["program_model"])
    lora = cfg["program"]["client"]
    wrapped = lora_wrap(model, base, lora["lora_rank"], lora["lora_alpha"],
                        tuple(lora["lora_targets"]))
    x, y = fed.data(2)
    np.testing.assert_allclose(np.asarray(model.apply(base, x)),
                               np.asarray(ref.forward(base, jnp.asarray(x), cfg)),
                               rtol=2e-5, atol=2e-5)
    got = wrapped.loss_and_metrics(adapters, {"x": x, "y": y})[0]
    want = ref.loss(adapters, jnp.asarray(x), jnp.asarray(y), cfg, base)
    assert float(got) == pytest.approx(float(want), rel=2e-6)


def test_the_program_freezes_the_base_it_is_handed(tiny):
    """Built by the harness, the system's round 0 forward is the handed
    base's own (``B`` starts at zero), and after a round in which the
    adapters move, the base under them has not."""
    import system

    cfg, ref, traffic, fed = _tiny_lm(tiny, seed=2**33 + 5)
    weights = refm.init_weights(ref, cfg, 2**33 + 5)
    frozen = refm.init_frozen(ref, cfg, 2**33 + 5)
    start, base = jax.device_get(weights), jax.device_get(frozen)
    trainer = system.build(cfg, traffic, fed, weights, 2**33 + 5, frozen)
    del weights, frozen
    model = get_model(cfg["program_model"])
    x, _ = fed.data(0)
    want = np.asarray(model.apply(base, x))
    np.testing.assert_array_equal(np.asarray(trainer.model.apply(start, x)), want)
    trainer.run_round(0)
    after = system.host_params(trainer)
    assert any(np.any(a != b) for a, b in zip(jax.tree_util.tree_leaves(after),
                                              jax.tree_util.tree_leaves(start)))
    no_b = jax.tree_util.tree_map_with_path(
        lambda p, a: np.zeros_like(a) if jax.tree_util.keystr(p).endswith("['b']") else a, after)
    np.testing.assert_array_equal(np.asarray(trainer.model.apply(no_b, x)), want)


def test_the_base_is_freed_with_the_system(tiny):
    """Once the system is freed as a run frees it before the reference,
    nothing holds the base it was handed: the reference's own base is then
    the only one on the chip."""
    import gc
    import weakref

    import system

    cfg, ref, traffic, fed = _tiny_lm(tiny, seed=11)
    frozen = refm.init_frozen(ref, cfg, 11)
    alive = [weakref.ref(a) for a in jax.tree_util.tree_leaves(frozen)]
    trainer = system.build(cfg, traffic, fed, refm.init_weights(ref, cfg, 11), 11, frozen)
    del frozen
    trainer.run_round(0)
    system.block(trainer)
    assert all(r() is not None for r in alive)
    del trainer
    gc.collect()
    assert system.release() == 0
    assert all(r() is None for r in alive)


def test_release_deletes_a_base_the_program_still_holds(tiny):
    """A leaf of the handed base that something still holds after the
    program's caches are dropped is deleted from the device, so the
    reference's base is the only one on the chip whatever the program
    keeps."""
    import system

    cfg, ref, traffic, fed = _tiny_lm(tiny, seed=12)
    frozen = refm.init_frozen(ref, cfg, 12)
    held = jax.tree_util.tree_leaves(frozen)[0]
    trainer = system.build(cfg, traffic, fed, refm.init_weights(ref, cfg, 12), 12, frozen)
    del frozen, trainer
    assert system.release() == 1
    assert held.is_deleted()


def test_the_base_is_handed_out_once(tiny):
    """A program that asks its model for the base a second time gets an
    error, not a base of its own."""
    import system

    cfg, ref, _, _ = _tiny_lm(tiny, seed=13)
    model, _ = system._handing(get_model(cfg["program_model"]),
                               refm.init_frozen(ref, cfg, 13))
    model.init(jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="second time"):
        model.init(jax.random.PRNGKey(0))
