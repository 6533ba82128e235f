"""Every Pallas kernel compiles for a TPU v5e at real widths.

The interpret-mode oracle tests cannot see what the TPU compiler refuses
(block shapes off the (8, 128) tiling, VMEM over-use, unsupported ops), so
each kernel is lowered with ``interpret=False`` against a *described*
``v5e:2x2`` topology and compiled ahead of time — no chip needed, about
two seconds each.  A compiled kernel shows up as a ``tpu_custom_call`` in
the optimized HLO.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU compiler library, so
describing it while modules are collected would make the other workers of
a parallel run fail.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (attention, fedavg_agg, quant, rwkv6_scan,
                           stc_topk)

RESNET18_D = 11_178_762     # cifar_resnet18 params: one flattened update
COHORT = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32 = jnp.float32
KERNELS = {
    "fedavg_aggregate": (
        lambda u, w: fedavg_agg.fedavg_aggregate(u, w, interpret=False),
        [((COHORT, RESNET18_D), F32), ((COHORT,), F32)]),
    "fedavg_aggregate_tree": (
        lambda u, w: fedavg_agg.fedavg_aggregate_tree(
            u, w, fanout=4, interpret=False),
        [((COHORT, RESNET18_D), F32), ((COHORT,), F32)]),
    "stc_compress": (
        lambda x: stc_topk.stc_compress(x, 0.01, interpret=False),
        [((RESNET18_D,), F32)]),
    "stc_compress_batched": (
        lambda x: stc_topk.stc_compress_batched(x, 0.01, interpret=False),
        [((COHORT, RESNET18_D), F32)]),
    "int8_roundtrip_batched": (
        lambda x: quant.int8_roundtrip_batched(x, interpret=False),
        [((COHORT, RESNET18_D), F32)]),
    "quantize": (
        lambda x: quant.quantize(x, interpret=False),
        [((RESNET18_D,), F32)]),
    "dequantize": (
        functools.partial(quant.dequantize, shape=(RESNET18_D,),
                          interpret=False),
        [((-(-RESNET18_D // quant.TILE_SEG) * quant.TILE_R, quant.TILE_C),
          jnp.int8),
         ((-(-RESNET18_D // quant.TILE_SEG), 1), F32)]),
    # glm4-9b attention at a 2k context: 32 heads of 128
    "flash_attention_fwd": (
        lambda q, k, v: attention.flash_attention(q, k, v, interpret=False),
        [((1, 32, 2048, 128), F32)] * 3),
    "flash_attention_bwd": (
        lambda q, k, v: jax.grad(lambda q, k, v: attention.flash_attention(
            q, k, v, interpret=False).sum(), argnums=(0, 1, 2))(q, k, v),
        [((1, 32, 2048, 128), F32)] * 3),
    # rwkv6-1.6b time mix: 32 heads of 64
    "wkv6": (
        lambda r, k, v, w, u, s0: rwkv6_scan.wkv6(r, k, v, w, u, s0,
                                                  interpret=False),
        [((1, 256, 32, 64), F32)] * 4 + [((32, 64), F32),
                                         ((1, 32, 64, 64), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    hlo = _compile_hlo(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo, f"{name}: no compiled Pallas kernel"
