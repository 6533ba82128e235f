"""Chunked WKV6 recurrence as a Pallas TPU kernel.

RWKV-6's data-dependent-decay recurrence is the compute hot spot of the
``rwkv6-1.6b`` assigned architecture.  TPU mapping: grid over (batch, head);
each grid cell keeps one head's (T, hd) slices of r/k/v/log-decay resident
in VMEM and walks the sequence in CHUNK=64 blocks with the recurrent state
(hd, hd) carried in registers through a ``fori_loop``:

  * cross-chunk term  : (CHUNK, hd) x (hd, hd) matmul against the state,
  * intra-chunk term  : exact log-space pairwise gates (CHUNK, CHUNK, hd)
                        — numerically safe, exponents always <= 0,
  * state update      : rank-CHUNK update k_dec^T @ v on the MXU.

VMEM budget per cell at T=4096, hd=64: 4 x 1 MB inputs + 1 MB output +
1 MB gate scratch ~ 6 MB < 16 MB v5e VMEM.  ``repro.models.rwkv6.
wkv6_chunked`` is the pure-jnp oracle (same chunk algorithm, vectorized over
batch/heads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

CHUNK = 64
# f32-exact MXU passes: the recurrence compounds any bf16 rounding
_EXACT = jax.lax.Precision.HIGHEST


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                 *, T: int, hd: int):
    L = CHUNK
    n_chunks = T // L
    u = u_ref[0].astype(jnp.float32)               # (1, hd)
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri_incl = (col <= row).astype(jnp.float32)    # inclusive prefix sum
    strict = (jax.lax.broadcasted_iota(jnp.int32, (L, L, hd), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (L, L, hd), 0))

    def chunk_body(i, S_t):
        # S_t is the state transposed, (hd_v, hd_k): the per-key decay
        # then scales its columns with a (1, hd) row
        sl = pl.ds(pl.multiple_of(i * L, L), L)
        rb = r_ref[0, 0, sl, :].astype(jnp.float32)          # (L, hd)
        kb = k_ref[0, 0, sl, :].astype(jnp.float32)
        vb = v_ref[0, 0, sl, :].astype(jnp.float32)
        wb = w_ref[0, 0, sl, :].astype(jnp.float32)          # log decay
        cw = jax.lax.dot(tri_incl, wb, precision=_EXACT,
                         preferred_element_type=jnp.float32)
        cw_excl = cw - wb
        cw_last = cw[L - 1:L, :]                             # (1, hd)
        # cross-chunk: decayed read of the carried state
        q_dec = rb * jnp.exp(cw_excl)
        y_inter = jax.lax.dot_general(q_dec, S_t, (((1,), (1,)), ((), ())),
                                      precision=_EXACT,
                                      preferred_element_type=jnp.float32)
        # intra-chunk: exact pairwise gates, exponent <= 0 for s < t;
        # mask BEFORE exp (future positions have diff > 0 -> inf * 0 = nan)
        diff = cw_excl[:, None, :] - cw[None, :, :]          # (L, L, hd)
        gate = jnp.exp(jnp.where(strict, diff, -1e30))
        scores = jnp.sum(rb[:, None, :] * gate * kb[None, :, :], axis=-1)
        y_intra = jax.lax.dot(scores, vb, precision=_EXACT,
                              preferred_element_type=jnp.float32)
        y_diag = jnp.sum(rb * u * kb, axis=-1, keepdims=True) * vb
        y_ref[0, 0, sl, :] = (y_inter + y_intra + y_diag).astype(
            y_ref.dtype)
        # state to chunk end
        k_dec = kb * jnp.exp(cw_last - cw)
        return jnp.exp(cw_last) * S_t + jax.lax.dot_general(
            vb, k_dec, (((0,), (0,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)

    S_t = s0_ref[0, 0].astype(jnp.float32)
    S_t = jax.lax.fori_loop(0, n_chunks, chunk_body, S_t)
    sT_ref[0, 0] = S_t


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r, k, v, logw, u, s0, interpret: Optional[bool] = None):
    """r,k,v,logw: (B, T, H, hd) f32; u: (H, hd); s0: (B, H, hd, hd).
    Returns (y (B,T,H,hd) f32, sT (B,H,hd,hd) f32).  T % 64 == 0.

    The kernel sees head-major (B, H, T, hd) sequences and the transposed
    state, so every block's last two dims span whole array dims (the TPU
    tiles them by (8, 128) otherwise)."""
    B, T, H, hd = r.shape
    assert T % CHUNK == 0, (T, CHUNK)
    heads = lambda a: jnp.swapaxes(a.astype(jnp.float32), 1, 2)
    seq_spec = pl.BlockSpec((1, 1, T, hd), lambda b, h: (b, h, 0, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h: (b, h, 0, 0))
    kern = functools.partial(_wkv6_kernel, T=T, hd=hd)
    y, sT_t = pl.pallas_call(
        kern,
        grid=(B, H),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, hd), lambda b, h: (h, 0, 0)),  # u
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(heads(r), heads(k), heads(v), heads(logw),
      u.astype(jnp.float32).reshape(H, 1, hd),
      jnp.swapaxes(s0.astype(jnp.float32), 2, 3))
    return jnp.swapaxes(y, 1, 2), jnp.swapaxes(sT_t, 2, 3)
