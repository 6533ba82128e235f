"""Public wrappers for the Pallas kernels.

``interpret=None`` (the default everywhere) compiles the kernels when the
default backend is a TPU and runs them in the Pallas interpreter elsewhere
(:func:`repro.kernels.resolve_interpret`); pass a bool to force either.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import attention, fedavg_agg, quant, rwkv6_scan, stc_topk


def fedavg_aggregate(updates, weights, interpret: Optional[bool] = None,
                     staleness=None, staleness_power: float = 0.5):
    return fedavg_agg.fedavg_aggregate(
        updates, weights, interpret=interpret,
        staleness=staleness, staleness_power=staleness_power)


def stc_compress(x, keep_frac: float = 0.01,
                 interpret: Optional[bool] = None):
    return stc_topk.stc_compress(x, keep_frac, interpret=interpret)


def stc_compress_batched(x, keep_frac: float = 0.01,
                         interpret: Optional[bool] = None, mesh=None):
    """Stacked-cohort STC: (N, D) -> (sparsified (N, D), nnz (N,)).

    With ``mesh`` (1-D client mesh), each shard compresses its own client
    rows in place (no gather, no collective)."""
    if mesh is not None:
        return stc_topk.stc_compress_batched_sharded(
            x, float(keep_frac), mesh, interpret=interpret)
    return stc_topk.stc_compress_batched(x, float(keep_frac),
                                         interpret=interpret)


def int8_roundtrip_batched(x, interpret: Optional[bool] = None, mesh=None):
    """Stacked-cohort int8 quantize→dequantize with per-client scales:
    (N, D) -> (sent (N, D), scale (N,)); sharded per client row under
    ``mesh``."""
    if mesh is not None:
        return quant.int8_roundtrip_batched_sharded(x, mesh,
                                                    interpret=interpret)
    return quant.int8_roundtrip_batched(x, interpret=interpret)


def quantize(x, interpret: Optional[bool] = None):
    return quant.quantize(x, interpret=interpret)


def dequantize(q, s, shape, dtype=jnp.float32,
               interpret: Optional[bool] = None):
    return quant.dequantize(q, s, tuple(shape), dtype, interpret=interpret)


def flash_attention(q, k, v, causal: bool = True,
                    interpret: Optional[bool] = None):
    """Tiled online-softmax attention, (B, H, S, D) MHA layout, with a
    flash backward (probs recomputed from the saved log-sum-exp)."""
    return attention.flash_attention(
        q, k, v, causal=causal, interpret=interpret)


def wkv6(r, k, v, logw, u, s0, interpret: Optional[bool] = None):
    return rwkv6_scan.wkv6(
        r, k, v, logw, u, s0, interpret=interpret)
