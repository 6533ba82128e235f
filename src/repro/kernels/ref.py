"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.quant import TILE_C, TILE_R
from repro.kernels.stc_topk import BISECT_ITERS
from repro.kernels.stc_topk import TILE_C as STC_C
from repro.kernels.stc_topk import TILE_R as STC_R


def fedavg_ref(updates: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """(N, D), (N,) -> (D,)."""
    return jnp.einsum("n,nd->d", weights.astype(jnp.float32),
                      updates.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _stc_tile_ref(x, keep_frac, real):
    """One threshold tile; ``real`` is the tile's unpadded element count
    (f32), matching the kernel's real-count target."""
    ax = jnp.abs(x.astype(jnp.float32))
    target = jnp.maximum(jnp.round(jnp.float32(keep_frac)
                                   * real.astype(jnp.float32)), 1.0)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        count = jnp.sum((ax > mid).astype(jnp.float32))
        lo = jnp.where(count > target, mid, lo)
        hi = jnp.where(count > target, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body,
                               (jnp.zeros((), jnp.float32),
                                jnp.max(ax) + 1e-12))
    t = 0.5 * (lo + hi)
    mask = ax > t
    nnz = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    mu = jnp.sum(jnp.where(mask, ax, 0.0)) / nnz
    return jnp.where(mask, jnp.sign(x.astype(jnp.float32)) * mu, 0.0)


def stc_ref(x: jnp.ndarray, keep_frac: float = 0.01) -> jnp.ndarray:
    """Tile-local STC, bit-matching the kernel's per-tile bisection."""
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.size
    tile = STC_R * STC_C
    pad = (-flat.size) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    tiles = flat.reshape(-1, STC_R, STC_C)
    reals = jnp.clip(n - jnp.arange(tiles.shape[0]) * tile, 0, tile)
    out = jax.vmap(lambda t, r: _stc_tile_ref(t, keep_frac, r))(tiles, reals)
    return out.reshape(-1)[: flat.size - pad].reshape(shape).astype(x.dtype)


def stc_batched_ref(x: jnp.ndarray, keep_frac: float = 0.01):
    """Row-wise (per-client) tile-local STC on an (N, D) matrix + per-row
    nnz — oracle for ``stc_topk.stc_compress_batched``."""
    out = jax.vmap(lambda row: stc_ref(row, keep_frac))(x)
    return out, jnp.sum((out != 0).astype(jnp.float32), axis=1)


def int8_roundtrip_batched_ref(x: jnp.ndarray):
    """Row-wise per-tensor-scale int8 round trip on an (N, D) matrix —
    oracle for ``quant.int8_roundtrip_batched`` (and bit-identical to the
    sequential compression stage's ``int8_compress_array`` per row)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=1),
                        1e-12) * jnp.float32(1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / scale[:, None]), -127, 127)
    return q * scale[:, None], scale


def quantize_ref(x: jnp.ndarray):
    flat = x.reshape(-1)
    tile = TILE_R * TILE_C
    pad = (-flat.size) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    tiles = flat.reshape(-1, TILE_R, TILE_C).astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(tiles), axis=(1, 2)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(tiles / scales[:, None, None]), -127, 127)
    grid = tiles.shape[0]
    return (q.astype(jnp.int8).reshape(grid * TILE_R, TILE_C),
            scales.reshape(grid, 1))


def dequantize_ref(q, s, shape, dtype=jnp.float32):
    tiles = q.reshape(s.shape[0], TILE_R, TILE_C).astype(jnp.float32)
    out = tiles * s[:, :, None]
    size = 1
    for d in shape:
        size *= d
    return out.reshape(-1)[:size].reshape(shape).astype(dtype)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """Plain-softmax attention on (B, H, S, D) — oracle for
    ``kernels.attention.flash_attention`` (materializes the full (S, S)
    score matrix the flash kernel never forms)."""
    B, H, S, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(D))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def wkv6_ref(r, k, v, logw, u, s0):
    """Sequential (non-chunked) WKV6 recurrence — ground truth."""
    B, T, H, hd = r.shape
    f32 = jnp.float32
    r, k, v, logw = (a.astype(f32) for a in (r, k, v, logw))
    u = u.astype(f32)

    def step(S, inputs):
        rt, kt, vt, wt = inputs            # (B,H,hd)
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)
        y = jnp.einsum("bhi,bhij->bhj", rt, S + u[None, :, :, None] * kv)
        S = jnp.exp(wt)[..., None] * S + kv
        return S, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, logw))
    sT, ys = jax.lax.scan(step, s0.astype(f32), xs)
    return ys.transpose(1, 0, 2, 3), sT


def wkv6_chunked_ref(r, k, v, logw, u, s0):
    """The chunked pure-jnp path used by the model (oracle per DESIGN.md)."""
    from repro.models.rwkv6 import wkv6_chunked
    return wkv6_chunked(r, k, v, logw, u, s0)
