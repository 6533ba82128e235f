"""STC sparse-ternary compression Pallas kernels (paper compression stage).

Per-tile top-k by *threshold bisection* — the TPU adaptation of STC's
global magnitude top-k (DESIGN.md §2): a sort across a multi-GB update
vector is hostile to the TPU memory system, whereas 16 elementwise
count-reduce passes over a VMEM-resident tile are nearly free.  Each
8192-element tile independently:

  1. bisects a threshold t so ~keep_frac of the tile's *real* (unpadded)
     elements exceed t (16 iterations),
  2. computes mu = mean(|x| | |x| > t),
  3. emits sign(x) * mu where |x| > t, else 0.

Tile-local selection guarantees an *exact* per-tile sparsity budget (global
STC can concentrate its budget on one layer) — the trade-off is evaluated in
``benchmarks/bench_compression.py``.  The per-tile target counts the tile's
real elements (``clip(n - k*TILE, 0, TILE)``), so zero-padded tails don't
inflate the kept fraction of small tensors.  ``repro.kernels.ref.stc_ref``
is the bit-equivalent pure-jnp oracle, and
``repro.core.compression.stc_compress_array`` (the compression *stage*)
implements the same per-tile algorithm, so stage == kernel.

Two entry points:

* :func:`stc_compress` — dense 1-tensor variant, 1-D grid over (8, 1024)
  tiles of the flattened input.
* :func:`stc_compress_batched` — the stacked-cohort variant for the
  batched execution engine's in-program compression: a 2-D grid
  ``(client-chunks × D-tiles)`` over an (N, D) matrix (one flattened
  update row per client), like ``kernels/fedavg_agg``.  Each block is
  (TILE_B, TILE_SEG) and thresholds are per *row segment* of TILE_SEG
  elements — element groups identical to the dense kernel's 8192-element
  tiles, so per-client results match :func:`stc_compress` on each row.
  The D-tile axis is the fastest grid dimension and revisits a per-chunk
  (TILE_B, 1) ``nnz`` output block (zero at tile 0, accumulate after),
  emitting the per-client non-zero count for wire-size accounting without
  ever gathering the updates to the host.
  :func:`stc_compress_batched_sharded` runs the same kernel per shard of
  a 1-D client mesh (rows are independent — no collective needed).
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
TILE_SEG = TILE_R * TILE_C      # elements per threshold tile (8192)
TILE_B = 8                      # client rows per batched-kernel block
BISECT_ITERS = 16


def _tile_target(keep_frac: float, real):
    """Per-tile kept-count target from the tile's *real* element count.

    f32 arithmetic everywhere so the dense kernel, the batched kernel, the
    jnp oracle and the compression stage compute bit-identical targets."""
    return jnp.maximum(jnp.round(jnp.float32(keep_frac)
                                 * real.astype(jnp.float32)), 1.0)


def _stc_kernel(x_ref, o_ref, *, keep_frac: float, n_real: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (TILE_R, TILE_C)
    ax = jnp.abs(x)
    real = jnp.clip(n_real - i * TILE_SEG, 0, TILE_SEG)
    target = _tile_target(keep_frac, real)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        count = jnp.sum((ax > mid).astype(jnp.float32))
        lo = jnp.where(count > target, mid, lo)
        hi = jnp.where(count > target, hi, mid)
        return lo, hi

    lo = jnp.zeros((), jnp.float32)
    hi = jnp.max(ax) + 1e-12
    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    t = 0.5 * (lo + hi)
    mask = ax > t
    nnz = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    mu = jnp.sum(jnp.where(mask, ax, 0.0)) / nnz
    o_ref[...] = jnp.where(mask, jnp.sign(x) * mu, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("keep_frac", "interpret"))
def stc_compress(x: jnp.ndarray, keep_frac: float = 0.01,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dense STC: returns the sparsified/ternarized tensor (same shape)."""
    shape = x.shape
    flat = x.reshape(-1)
    n_real = flat.size
    pad = (-flat.size) % TILE_SEG
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = flat.size // TILE_SEG
    x2 = flat.reshape(grid * TILE_R, TILE_C)
    out = pl.pallas_call(
        functools.partial(_stc_kernel, keep_frac=keep_frac, n_real=n_real),
        grid=(grid,),
        in_specs=[pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x2)
    return out.reshape(-1)[: flat.size - pad].reshape(shape)


# ---------------------------------------------------------------------------
# Batched (stacked-cohort) variant: 2-D grid, per-client nnz
# ---------------------------------------------------------------------------


def _stc_batched_kernel(x_ref, o_ref, nnz_ref, *, keep_frac: float,
                        d_real: int, tile_d: int):
    j = pl.program_id(1)               # D-tile index (fastest dim)
    x = x_ref[...].astype(jnp.float32)              # (TILE_B, tile_d)
    ax = jnp.abs(x)
    real = jnp.clip(d_real - j * tile_d, 0, tile_d)
    target = _tile_target(keep_frac, real)          # scalar; rows share it

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        count = jnp.sum((ax > mid).astype(jnp.float32), axis=1,
                        keepdims=True)              # (TILE_B, 1)
        lo = jnp.where(count > target, mid, lo)
        hi = jnp.where(count > target, hi, mid)
        return lo, hi

    lo = jnp.zeros((x.shape[0], 1), jnp.float32)
    hi = jnp.max(ax, axis=1, keepdims=True) + 1e-12
    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    t = 0.5 * (lo + hi)
    mask = ax > t
    cnt = jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)
    mu = jnp.sum(jnp.where(mask, ax, 0.0), axis=1, keepdims=True) \
        / jnp.maximum(cnt, 1.0)
    o_ref[...] = jnp.where(mask, jnp.sign(x) * mu, 0.0).astype(o_ref.dtype)

    @pl.when(j == 0)
    def _zero():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)

    nnz_ref[...] += cnt


@functools.partial(jax.jit,
                   static_argnames=("keep_frac", "d_real", "interpret",
                                    "tile_d"))
def _stc_batched_padded(x: jnp.ndarray, keep_frac: float, d_real: int,
                        interpret: Optional[bool], tile_d: int):
    N, D = x.shape                      # pre-padded: N % TILE_B == D % tile_d == 0
    out, nnz = pl.pallas_call(
        functools.partial(_stc_batched_kernel, keep_frac=keep_frac,
                          d_real=d_real, tile_d=tile_d),
        grid=(N // TILE_B, D // tile_d),
        in_specs=[pl.BlockSpec((TILE_B, tile_d), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((TILE_B, tile_d), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_B, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x)
    return out, nnz


def stc_compress_batched(x: jnp.ndarray, keep_frac: float = 0.01,
                         interpret: Optional[bool] = None,
                         tile_d: int = TILE_SEG):
    """Sparsify a stacked (N, D) cohort update in one pallas_call.

    Args:
        x: (N, D) — one flattened update row per client.
        keep_frac: per-tile keep fraction (``client.stc_sparsity``).
        interpret: Pallas interpret mode; None compiles on a TPU and
            interprets elsewhere (:func:`repro.kernels.resolve_interpret`).
        tile_d: elements per per-row threshold tile; the default
            ``TILE_SEG`` (8192) makes each row's tiles the same element
            groups as the dense kernel / compression stage, so per-client
            results match the sequential path.

    Returns:
        ``(out, nnz)`` — out (N, D) f32 sparsified/ternarized, nnz (N,)
        f32 per-client non-zero counts (wire-size accounting).
    """
    N, D = x.shape
    pad_r = (-N) % TILE_B
    pad_c = (-D) % tile_d
    xp = x.astype(jnp.float32)
    if pad_r or pad_c:
        xp = jnp.pad(xp, ((0, pad_r), (0, pad_c)))
    out, nnz = _stc_batched_padded(xp, keep_frac, D, interpret, tile_d)
    return out[:N, :D], nnz[:N, 0]


@functools.lru_cache(maxsize=32)
def _stc_batched_sharded_program(mesh, axis: str, keep_frac: float,
                                 interpret: Optional[bool], tile_d: int):
    """Jitted shard_map program, cached per (mesh, keep_frac, tiling) —
    same rationale as ``fedavg_agg._sharded_program``: an uncached
    shard_map retraces every call."""
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import shard_map

    def body(x_loc):
        return stc_compress_batched(x_loc, keep_frac, interpret, tile_d)

    return jax.jit(shard_map(body, mesh, in_specs=(P(axis, None),),
                             out_specs=(P(axis, None), P(axis))))


def stc_compress_batched_sharded(x: jnp.ndarray, keep_frac: float, mesh,
                                 axis: str = "clients",
                                 interpret: Optional[bool] = None,
                                 tile_d: int = TILE_SEG):
    """Mesh-sharded :func:`stc_compress_batched`: each shard sparsifies its
    own client rows (rows are independent — no collective), so compressed
    updates never leave their device.  N must be divisible by ``mesh.size``
    (the batched engine bucket-pads the client dim to guarantee this)."""
    if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
        raise ValueError(
            f"stc_compress_batched_sharded needs a 1-D mesh with axis "
            f"{axis!r}, got axes {mesh.axis_names}")
    if x.shape[0] % mesh.size:
        raise ValueError(
            f"client dim {x.shape[0]} must be divisible by the mesh size "
            f"{mesh.size}")
    return _stc_batched_sharded_program(mesh, axis, float(keep_frac),
                                        interpret, tile_d)(x)
