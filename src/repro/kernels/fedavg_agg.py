"""FedAvg aggregation Pallas kernel: chunked streaming weighted sum.

The aggregation stage touches every parameter of every selected client once
per round — a pure memory-bound streaming reduction.  TPU mapping: the
flattened update matrix (N clients × D params) is tiled along *both* axes
with a 2-D grid ``(D-tiles × client-chunks)``; each grid step loads one
(TILE_N, TILE_D) block into VMEM and accumulates its weighted column sum
into the output tile:

    out[tile_d] += sum(w[chunk, None] * updates[chunk, tile_d], axis=0)

The client-chunk axis is the fastest grid dimension, so all chunks of one
D-tile revisit the same output block consecutively (the standard Pallas
accumulate pattern: zero the tile at chunk 0, ``+=`` afterwards).  Peak VMEM
is therefore TILE_N·TILE_D·4B (≈64 KB) regardless of cohort size, instead of
the previous N·TILE_D block that grew linearly with N.

N is padded up to a *bucket* (powers of two × TILE_N) with zero weights and
zero rows before the jitted inner call, so per-round cohort-size jitter
(e.g. 97, 100, 103 selected clients) hits one compiled program instead of
recompiling every round.  Zero-padding leaves the weighted sum unchanged and
keeps the weight total at 1.

Asynchronous (FedBuff-style) aggregation reuses both entry points unchanged:
staleness discounting is a *weight transform* (``fold_staleness``), applied
before bucket padding, so the streaming kernel and the mesh-sharded psum
path never see staleness — just a different normalized weight vector.  Both
``fedavg_aggregate`` and ``fedavg_aggregate_sharded`` accept an optional
per-client ``staleness`` vector and fold it in-place.

Hierarchical topology (``resources.aggregation_topology = "hierarchical"``):
:func:`fedavg_aggregate_tree` generalizes the flat weighted sum into an
edge→region→global reduction tree.  Clients are grouped into blocks of
``fanout``; each block is reduced to a weighted partial sum by the *same*
streaming tier reduction (the per-shard step of the flat path), and the
(G, D) partials are fed to the next tier until one row remains.  Because
every tier is linear in the weights, the tree computes the identical
convex combination — with ``fanout >= N`` the first tier *is* the flat
program, so the result is bit-equal; smaller fanouts only reassociate the
fp32 summation (equal to ~1e-6).  Staleness folding, fault masking, and
compressed stacked updates compose unchanged: they all act on the weight
vector or the update rows before the tree sees them.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_D = 2048
TILE_N = 8

#: traces of the jitted tree-aggregation program (contracts gate reads this
#: through :func:`tree_trace_count` — one trace per (bucket, fanout) pair,
#: zero retraces across rounds at fixed shapes)
_TREE_TRACES = 0


def tree_trace_count() -> int:
    """Process-wide count of hierarchical-aggregation program traces."""
    return _TREE_TRACES


def bucket_clients(n: int, tile_n: int = TILE_N) -> int:
    """Smallest power-of-two multiple of ``tile_n`` that holds ``n`` rows."""
    b = tile_n
    while b < n:  # flcheck: ignore[FLC202]  -- n is a static host int (shape)
        b *= 2
    return b


def pad_cohort(updates: jnp.ndarray, weights: jnp.ndarray,
               tile_n: int = TILE_N) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero-pad (N, D) updates + (N,) weights up to the N bucket.

    Padded rows carry weight 0, so the weighted sum — and the total weight —
    are unchanged.
    """
    n = updates.shape[0]
    nb = bucket_clients(n, tile_n)
    if nb == n:
        return updates, weights
    return (jnp.pad(updates, ((0, nb - n), (0, 0))),
            jnp.pad(weights, (0, nb - n)))


def fold_staleness(weights: jnp.ndarray, staleness: jnp.ndarray,
                   power: float = 0.5) -> jnp.ndarray:
    """Fold a staleness discount into a normalized weight vector.

    Args:
        weights: (N,) non-negative aggregation weights (e.g. FedAvg
            sample-count weights).
        staleness: (N,) model-version lag of each update — how many server
            aggregations happened between the update's dispatch and its
            application (0 = trained on the current model).
        power: discount exponent ``a``; each weight is scaled by
            ``1/(1+s)^a`` (FedBuff uses a=0.5; 0 disables discounting).

    Returns:
        (N,) f32 weights, rescaled to sum to 1 so downstream weighted sums
        (kernel, einsum, sharded psum) stay a convex combination.
    """
    w = jnp.asarray(weights, jnp.float32)
    s = jnp.asarray(staleness, jnp.float32)
    w = w * (1.0 + s) ** jnp.float32(-power)
    return w / jnp.sum(w)


def _agg_kernel(w_ref, u_ref, o_ref):
    j = pl.program_id(1)               # client-chunk index (fastest dim)

    @pl.when(j == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # (TILE_N, 1) weights broadcast over the (TILE_N, TILE_D) rows, summed
    # over the sublanes: an exact f32 weighted sum on the VPU (the MXU's
    # default f32 passes would round the updates to bf16)
    o_ref[...] += jnp.sum(w_ref[...] * u_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tile_d", "tile_n"))
def _aggregate_padded(updates: jnp.ndarray, weights: jnp.ndarray,
                      interpret: Optional[bool], tile_d: int,
                      tile_n: int) -> jnp.ndarray:
    N, D = updates.shape
    pad = (-D) % tile_d
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    Dp = D + pad
    out = pl.pallas_call(
        _agg_kernel,
        grid=(Dp // tile_d, N // tile_n),
        in_specs=[
            # weights as an (N, 1) column: the TPU tiles the last two
            # block dims by (8, 128) unless they span the whole array
            pl.BlockSpec((tile_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_n, tile_d), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, tile_d), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(weights.reshape(N, 1).astype(jnp.float32),
      updates.astype(jnp.float32))
    return out[0, :D]


def fedavg_aggregate(updates: jnp.ndarray, weights: jnp.ndarray,
                     interpret: Optional[bool] = None, tile_d: int = TILE_D,
                     tile_n: int = TILE_N,
                     staleness: Optional[jnp.ndarray] = None,
                     staleness_power: float = 0.5) -> jnp.ndarray:
    """Streaming weighted sum of client updates.

    Args:
        updates: (N, D) f32 — one flattened update vector per client.
        weights: (N,) aggregation weights summing to 1 (FedAvg sample
            weights; see :func:`repro.core.aggregation.fedavg_weights`).
        interpret: Pallas interpret mode; None compiles on a TPU and
            interprets elsewhere (:func:`repro.kernels.resolve_interpret`).
        tile_d, tile_n: VMEM block shape; peak VMEM is tile_n*tile_d*4 B
            regardless of N.
        staleness: optional (N,) per-update staleness; when given, weights
            are rescaled by ``1/(1+s)^staleness_power`` and renormalized
            (:func:`fold_staleness`) before padding — the async FedBuff path.
        staleness_power: discount exponent for ``staleness``.

    Returns:
        (D,) f32 weighted average.

    N is bucket-padded (power-of-two multiples of ``tile_n``, zero weights)
    *outside* the jitted inner function, so varying per-round cohort sizes
    within one bucket reuse a single compiled program.
    """
    weights = weights.astype(jnp.float32)
    if staleness is not None:
        weights = fold_staleness(weights, staleness, staleness_power)
    updates, weights = pad_cohort(updates.astype(jnp.float32),
                                  weights, tile_n)
    return _aggregate_padded(updates, weights, interpret, tile_d, tile_n)


def _tier_reduce(updates: jnp.ndarray, weights: jnp.ndarray,
                 use_kernel: bool, interpret: Optional[bool], tile_d: int,
                 tile_n: int) -> jnp.ndarray:
    """One tier of the reduction tree: (G, F, D) x (G, F) -> (G, D).

    Each group is reduced by the same streaming weighted sum the flat path
    uses — either the chunked Pallas kernel (sequentially over groups via
    ``lax.map``, so one compiled kernel instance serves every group) or the
    einsum fallback.
    """
    if use_kernel:  # flcheck: ignore[FLC201]  -- static argname, resolved at trace time
        return jax.lax.map(
            lambda wu: _aggregate_padded(wu[1], wu[0], interpret, tile_d,
                                         tile_n),
            (weights, updates))
    return jnp.einsum("gf,gfd->gd", weights, updates,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("fanout", "use_kernel",
                                             "interpret", "tile_d", "tile_n"))
def _tree_padded(updates: jnp.ndarray, weights: jnp.ndarray, fanout: int,
                 use_kernel: bool, interpret: Optional[bool], tile_d: int,
                 tile_n: int) -> jnp.ndarray:
    """Edge→region→global reduction tree over (N, D) rows.

    The edge tier folds the aggregation weights into per-group partial
    sums; deeper tiers sum the partials (weight 1 each) until one row
    remains.  All shapes are static, so the tier loop unrolls at trace
    time into a fixed program.
    """
    global _TREE_TRACES
    _TREE_TRACES += 1
    u = updates.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    group = bucket_clients(fanout, tile_n) if use_kernel else fanout
    while u.shape[0] > 1:
        n = u.shape[0]
        g = -(-n // group)
        pad = g * group - n
        if pad:                        # zero rows + zero weights: no-op terms
            u = jnp.pad(u, ((0, pad), (0, 0)))
            w = jnp.pad(w, (0, pad))
        u = _tier_reduce(u.reshape(g, group, u.shape[1]),
                         w.reshape(g, group), use_kernel, interpret,
                         tile_d, tile_n)
        w = jnp.ones((g,), jnp.float32)    # partials already carry weight
    return u[0]


def fedavg_aggregate_tree(updates: jnp.ndarray, weights: jnp.ndarray,
                          fanout: int = 0, interpret: Optional[bool] = None,
                          use_kernel: bool = True, tile_d: int = TILE_D,
                          tile_n: int = TILE_N,
                          staleness: Optional[jnp.ndarray] = None,
                          staleness_power: float = 0.5) -> jnp.ndarray:
    """Hierarchical (edge→region→global) weighted sum of client updates.

    Args:
        updates: (N, D) f32 — one flattened update vector per client.
        weights: (N,) aggregation weights summing to 1.
        fanout: children per tree node.  ``0`` picks ``ceil(sqrt(N))``
            (two balanced tiers); ``fanout >= N`` short-circuits to the
            flat program, making the result bit-equal to
            :func:`fedavg_aggregate`.
        interpret, use_kernel, tile_d, tile_n: tier implementation — the
            streaming Pallas kernel per group (``use_kernel``) or einsum.
        staleness, staleness_power: optional FedBuff discount, folded into
            ``weights`` exactly as on the flat path.

    Returns:
        (D,) f32 weighted average.
    """
    n = int(updates.shape[0])  # flcheck: ignore[FLC102]  -- shape, not device data
    weights = weights.astype(jnp.float32)
    if staleness is not None:
        weights = fold_staleness(weights, staleness, staleness_power)
    if fanout <= 0:
        fanout = max(2, int(math.ceil(math.sqrt(n))))
    if fanout >= n:                    # one group == the flat program
        if use_kernel:
            return fedavg_aggregate(updates, weights, interpret=interpret,
                                    tile_d=tile_d, tile_n=tile_n)
        return jnp.einsum("n,nd->d", weights,
                          updates.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    updates, weights = pad_cohort(updates.astype(jnp.float32), weights,
                                  tile_n if use_kernel else 1)
    return _tree_padded(updates, weights, int(fanout), use_kernel,
                        interpret, tile_d, tile_n)


def fedavg_aggregate_sharded(updates: jnp.ndarray, weights: jnp.ndarray,
                             mesh, axis: str = "clients",
                             interpret: Optional[bool] = None,
                             tile_d: int = TILE_D,
                             tile_n: int = TILE_N,
                             staleness: Optional[jnp.ndarray] = None,
                             staleness_power: float = 0.5,
                             fanout: int = 0) -> jnp.ndarray:
    """Mesh-sharded weighted sum: per-shard partials + ``psum`` epilogue.

    Args:
        updates: (N, D) with the client dim sharded (or shardable) over the
            1-D ``mesh``.
        weights: (N,) aggregation weights summing to 1.
        mesh: 1-D ``jax.sharding.Mesh`` whose single axis is ``axis``.
        axis: mesh axis name carrying the client dimension.
        interpret, tile_d, tile_n: as in :func:`fedavg_aggregate`.
        staleness, staleness_power: optional per-update staleness discount,
            folded into ``weights`` (:func:`fold_staleness`) before
            sharding/padding — the async FedBuff path reuses this function
            unchanged.
        fanout: ``> 0`` makes each shard reduce its local rows through the
            hierarchical tree (:func:`fedavg_aggregate_tree` tiers) before
            the cross-shard ``psum`` top tier; ``0`` keeps the flat
            per-shard partial.  With ``fanout >= rows-per-shard`` the tree
            collapses to the flat partial, so results stay bit-equal.

    Returns:
        (D,) f32 weighted average, replicated on every device.

    Each shard streams its own client rows through the chunked
    accumulation (so no device ever materializes another shard's updates),
    then one ``psum`` of the (D,) partial weighted sums — D·4 bytes per
    device instead of moving all N·D·4 update bytes to one device.  N is
    zero-padded to a power-of-two multiple of ``tile_n * mesh.size`` so
    shards stay equal and padded rows contribute nothing.
    """
    if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
        raise ValueError(
            f"fedavg_aggregate_sharded needs a 1-D mesh with axis "
            f"{axis!r}, got axes {mesh.axis_names}")
    nshards = mesh.size
    updates = updates.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    if staleness is not None:
        weights = fold_staleness(weights, staleness, staleness_power)
    updates, weights = pad_cohort(updates, weights, tile_n * nshards)
    if fanout >= updates.shape[0] // nshards:
        fanout = 0                     # tree collapses to the flat partial
    return _sharded_program(mesh, axis, interpret, tile_d, tile_n,
                            int(fanout))(weights, updates)


@functools.lru_cache(maxsize=32)
def _sharded_program(mesh, axis: str, interpret: Optional[bool], tile_d: int,
                     tile_n: int, fanout: int = 0):
    """Jitted shard_map program, cached per (mesh, tiling, fanout) — an
    uncached shard_map retraces every call (~200ms/round), defeating the
    bucket-padding one-compiled-program design."""
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import shard_map

    def shard_body(w_loc, u_loc):
        if fanout > 0:                 # local tree tiers, psum top tier
            part = _tree_padded(u_loc, w_loc, fanout, True, interpret,
                                tile_d, tile_n)
        else:
            part = _aggregate_padded(u_loc, w_loc, interpret, tile_d, tile_n)
        return jax.lax.psum(part, axis)

    return jax.jit(shard_map(shard_body, mesh,
                             in_specs=(P(axis), P(axis, None)),
                             out_specs=P()))
