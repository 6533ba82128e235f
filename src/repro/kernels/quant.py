"""Int8 symmetric quantization Pallas kernels (compression stage, wire int8).

Per-tile (8, 1024) scale = max|x|/127; quantize and dequantize as separate
kernels so the quantized representation can cross the (simulated) wire.
Tile-local scales bound the quantization error per 8K-element block — the
TPU-native replacement for per-tensor scales on multi-GB updates.

Batched (stacked-cohort) variant for the batched execution engine's
in-program compression: :func:`int8_roundtrip_batched` takes the stacked
(N, D) update matrix (one flattened update row per client) and returns the
quantize→dequantize round trip with one **per-row** scale — the exact
per-tensor-scale semantics of the sequential compression stage
(``repro.core.compression.int8_compress_array``), so per-client results
are bit-identical to the per-client path.  Two chained 2-D-grid kernels
(client-chunks × D-tiles, like ``fedavg_agg``): a row-max accumulation
pass (the D-tile axis is the fastest grid dimension and revisits a
per-chunk (TILE_B, 1) max block) and a fused quantize+dequantize pass.
Nothing ever gathers to the host; :func:`int8_roundtrip_batched_sharded`
runs the same kernels per shard of a 1-D client mesh (rows are
independent — no collective).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_R = 8
TILE_C = 1024
TILE_SEG = TILE_R * TILE_C      # elements per batched-kernel D-tile
TILE_B = 8                      # client rows per batched-kernel block


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.full(s_ref.shape, scale, jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[0]).astype(
        o_ref.dtype)


def _tile(x):
    flat = x.reshape(-1)
    tile = TILE_R * TILE_C
    pad = (-flat.size) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = flat.size // tile
    return flat.reshape(grid * TILE_R, TILE_C), grid, pad


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jnp.ndarray, interpret: Optional[bool] = None):
    """-> (q int8 tiled (R, C), scales (grid, 1)) for dequantize."""
    x2, grid, pad = _tile(x)
    # one scale per tile, as a (1, 1) block of a (grid, 1, 1) array: the
    # TPU accepts a block whose last two dims span the whole array
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.int8),
            jax.ShapeDtypeStruct((grid, 1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x2)
    return q, s.reshape(grid, 1)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def dequantize(q: jnp.ndarray, s: jnp.ndarray, shape, dtype=jnp.float32,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    grid = s.shape[0]
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, dtype),
        interpret=resolve_interpret(interpret),
    )(q, s.reshape(grid, 1, 1))
    size = 1
    for d in shape:
        size *= d
    return out.reshape(-1)[:size].reshape(shape)


# ---------------------------------------------------------------------------
# Batched (stacked-cohort) variant: per-row (per-client) scales
# ---------------------------------------------------------------------------


def _rowmax_kernel(x_ref, m_ref):
    j = pl.program_id(1)               # D-tile index (fastest dim)

    @pl.when(j == 0)
    def _zero():
        m_ref[...] = jnp.zeros_like(m_ref)

    ax = jnp.abs(x_ref[...].astype(jnp.float32))    # (TILE_B, tile_d)
    m_ref[...] = jnp.maximum(m_ref[...], jnp.max(ax, axis=1, keepdims=True))


def _qdq_kernel(x_ref, s_ref, o_ref):
    s = s_ref[...]                                  # (TILE_B, 1) scales
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / s),
                 -127.0, 127.0)
    o_ref[...] = (q * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _int8_roundtrip_padded(x: jnp.ndarray, interpret: Optional[bool],
                           tile_d: int):
    N, D = x.shape                     # pre-padded to the block grid
    grid = (N // TILE_B, D // tile_d)
    m = pl.pallas_call(
        _rowmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_B, tile_d), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((TILE_B, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x)
    # explicit reciprocal multiply: XLA strength-reduces `m / 127.0` to a
    # 1-ulp-off reciprocal multiply under jit, which would break bitwise
    # agreement with the eager sequential stage (int8_compress_array)
    scale = jnp.maximum(m, 1e-12) * jnp.float32(1.0 / 127.0)
    sent = pl.pallas_call(
        _qdq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_B, tile_d), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_B, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_B, tile_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, scale)
    return sent, scale


def int8_roundtrip_batched(x: jnp.ndarray, interpret: Optional[bool] = None,
                           tile_d: int = TILE_SEG):
    """Quantize→dequantize a stacked (N, D) cohort update with per-row
    (= per-client per-tensor) scales.

    Returns ``(sent, scale)`` — sent (N, D) f32 round-tripped values
    (bit-identical to the sequential per-client int8 stage), scale (N,)
    f32 per-client scales.  Padded rows/columns are zeros: they never win
    the row max, quantize to 0, and are sliced off before returning.
    """
    N, D = x.shape
    pad_r = (-N) % TILE_B
    pad_c = (-D) % tile_d
    xp = x.astype(jnp.float32)
    if pad_r or pad_c:
        xp = jnp.pad(xp, ((0, pad_r), (0, pad_c)))
    sent, scale = _int8_roundtrip_padded(xp, interpret, tile_d)
    return sent[:N, :D], scale[:N, 0]


@functools.lru_cache(maxsize=32)
def _int8_batched_sharded_program(mesh, axis: str, interpret: Optional[bool],
                                  tile_d: int):
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import shard_map

    def body(x_loc):
        return int8_roundtrip_batched(x_loc, interpret, tile_d)

    return jax.jit(shard_map(body, mesh, in_specs=(P(axis, None),),
                             out_specs=(P(axis, None), P(axis))))


def int8_roundtrip_batched_sharded(x: jnp.ndarray, mesh,
                                   axis: str = "clients",
                                   interpret: Optional[bool] = None,
                                   tile_d: int = TILE_SEG):
    """Mesh-sharded :func:`int8_roundtrip_batched` (per-shard rows, no
    collective).  N must be divisible by ``mesh.size``."""
    if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
        raise ValueError(
            f"int8_roundtrip_batched_sharded needs a 1-D mesh with axis "
            f"{axis!r}, got axes {mesh.axis_names}")
    if x.shape[0] % mesh.size:
        raise ValueError(
            f"client dim {x.shape[0]} must be divisible by the mesh size "
            f"{mesh.size}")
    return _int8_batched_sharded_program(mesh, axis, interpret, tile_d)(x)
