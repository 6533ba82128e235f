"""Memory-efficient (tiled online-softmax) attention Pallas kernels.

The LLM fine-tuning hot path (``models/attention.chunked_causal_attention``
behind the ``REPRO_FLASH_ATTN`` flag).  Instead of materializing the
(S, S) score matrix, the forward kernel streams key/value tiles through
VMEM with the online-softmax recurrence (running row max ``m``, running
denominator ``l``, rescaled accumulator) — activation memory is
O(TILE_Q · TILE_K) per grid step regardless of sequence length, the same
trade FlashAttention makes on GPUs.

Grid layout follows the repo's accumulate idiom (``fedavg_agg``,
``stc_topk``): the key-tile axis is the **fastest** grid dimension and
revisits the (batch·head, q-tile) output block — zero at the first key
tile, rescale+accumulate after, normalize at the last key tile.  The
backward pass is the standard flash backward: probabilities are
*recomputed* per tile from the saved log-sum-exp (no O(S²) residual),
``delta = rowsum(dO · O)``, one kernel accumulating dQ over key tiles and
one accumulating dK/dV over query tiles.

All kernels take (B·H, S, D) with MHA-expanded heads; the GQA wrapper in
``models/attention`` repeats kv heads per group.  Sequences and head
dims are zero-padded to tile multiples and masked by *global* indices,
so odd/unaligned S and D are exact, not approximated.
``repro.kernels.ref.attention_ref`` is the pure-jnp oracle.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_Q = 64
TILE_K = 64
NEG_INF = -1e30
_TINY = 1e-30          # denominator floor for fully-masked (padded) rows


def _tile_mask(i, j, tile_q, tile_k, s_real: int, causal: bool):
    """(tile_q, tile_k) bool validity mask from *global* row/col indices."""
    qi = i * tile_q + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
    kj = j * tile_k + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1)
    mask = (qi < s_real) & (kj < s_real)
    if causal:
        mask &= kj <= qi
    return mask


# ---------------------------------------------------------------------------
# Forward: online softmax, key tiles fastest
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, lse_ref, *,
                scale: float, causal: bool, s_real: int, nk: int):
    i = pl.program_id(1)               # query tile
    j = pl.program_id(2)               # key tile (fastest — revisits outputs)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        lse_ref[...] = jnp.zeros_like(lse_ref)

    # causal: key tiles strictly above the diagonal band contribute nothing
    live = (j * TILE_K <= i * TILE_Q + TILE_Q - 1) if causal \
        else (j <= nk - 1)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)                   # (tq, Dp)
        k = k_ref[0].astype(jnp.float32)                   # (tk, Dp)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (tq, tk)
        mask = _tile_mask(i, j, TILE_Q, TILE_K, s_real, causal)
        m_prev = m_ref[0]                                  # (tq, 1)
        l_prev = l_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(jnp.where(mask, s, NEG_INF),
                                            axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = o_ref[0] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[0], _TINY)
        o_ref[0] = o_ref[0] / l
        lse_ref[0] = m_ref[0] + jnp.log(l)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "s_real", "interpret"))
def _fwd_padded(q, k, v, scale: float, causal: bool, s_real: int,
                interpret: Optional[bool]):
    BH, Sp, Dp = q.shape
    nq, nk = Sp // TILE_Q, Sp // TILE_K
    # per-row stats as (BH, Sp, 1) columns: the TPU tiles the last two
    # block dims by (8, 128) unless they span the whole array
    row_spec = pl.BlockSpec((1, TILE_Q, 1), lambda b, i, j: (b, i, 0))
    out, _m, _l, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          s_real=s_real, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, TILE_Q, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, TILE_K, Dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, TILE_K, Dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_Q, Dp), lambda b, i, j: (b, i, 0)),
            row_spec, row_spec, row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, Dp), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sp, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: recompute probs from saved lse; two accumulate kernels
# ---------------------------------------------------------------------------


def _p_tile(q_ref, k_ref, lse_ref, i, j, *, scale, causal, s_real):
    """Recomputed normalized probability tile p_ij = exp(s_ij - lse_i)."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    mask = _tile_mask(i, j, TILE_Q, TILE_K, s_real, causal)
    return jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale: float, causal: bool, s_real: int):
    i = pl.program_id(1)               # query tile
    j = pl.program_id(2)               # key tile (fastest — revisits dq)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    live = (j * TILE_K <= i * TILE_Q + TILE_Q - 1) if causal \
        else (j >= 0)

    @pl.when(live)
    def _accumulate():
        p = _p_tile(q_ref, k_ref, lse_ref, i, j, scale=scale, causal=causal,
                    s_real=s_real)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_ref[0] += jax.lax.dot(ds, k_ref[0].astype(jnp.float32),
                                 preferred_element_type=jnp.float32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale: float, causal: bool, s_real: int):
    j = pl.program_id(1)               # key tile
    i = pl.program_id(2)               # query tile (fastest — revisits dk/dv)

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    live = (i * TILE_Q + TILE_Q - 1 >= j * TILE_K) if causal \
        else (i >= 0)

    @pl.when(live)
    def _accumulate():
        p = _p_tile(q_ref, k_ref, lse_ref, i, j, scale=scale, causal=causal,
                    s_real=s_real)
        do = do_ref[0].astype(jnp.float32)
        dv_ref[0] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_ref[0] += jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "s_real", "interpret"))
def _bwd_padded(q, k, v, do, lse, delta, scale: float, causal: bool,
                s_real: int, interpret: Optional[bool]):
    BH, Sp, Dp = q.shape
    nq, nk = Sp // TILE_Q, Sp // TILE_K
    q_spec = pl.BlockSpec((1, TILE_Q, Dp), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, TILE_K, Dp), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, TILE_Q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          s_real=s_real),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sp, Dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    # dk/dv: key tiles on the slow axis, query tiles revisit the outputs
    qT_spec = pl.BlockSpec((1, TILE_Q, Dp), lambda b, j, i: (b, i, 0))
    kT_spec = pl.BlockSpec((1, TILE_K, Dp), lambda b, j, i: (b, j, 0))
    rowT_spec = pl.BlockSpec((1, TILE_Q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          s_real=s_real),
        grid=(BH, nk, nq),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rowT_spec, rowT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Sp, Dp), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sp, Dp), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry: custom_vjp over padded kernels
# ---------------------------------------------------------------------------


def _pad(x, sp, dp):
    _, S, D = x.shape
    # zero pad widths are elided by XLA, so the aligned case costs nothing
    return jnp.pad(x, ((0, 0), (0, sp - S), (0, dp - D)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, interpret: Optional[bool]):
    out, _ = _flash_fwd(q, k, v, causal, interpret)
    return out


def _flash_fwd(q, k, v, causal: bool, interpret: Optional[bool]):
    BH, S, D = q.shape
    sp = -(-S // TILE_Q) * TILE_Q
    dp = max(8, -(-D // 8) * 8)
    scale = 1.0 / math.sqrt(D)         # the *real* head dim sets the scale
    out, lse = _fwd_padded(
        _pad(q, sp, dp).astype(jnp.float32),
        _pad(k, sp, dp).astype(jnp.float32),
        _pad(v, sp, dp).astype(jnp.float32),
        scale, causal, S, interpret)
    return out[:, :S, :D].astype(q.dtype), (q, k, v, out, lse)


def _flash_bwd(causal: bool, interpret: Optional[bool], res, g):
    q, k, v, out_p, lse = res             # out_p/lse are padded f32
    BH, S, D = q.shape
    sp, dp = out_p.shape[1], out_p.shape[2]
    scale = 1.0 / math.sqrt(D)
    do = _pad(g.astype(jnp.float32), sp, dp)
    # (BH, Sp, 1); zero on padded rows
    delta = jnp.sum(do * out_p, axis=-1, keepdims=True)
    dq, dk, dv = _bwd_padded(
        _pad(q, sp, dp).astype(jnp.float32),
        _pad(k, sp, dp).astype(jnp.float32),
        _pad(v, sp, dp).astype(jnp.float32),
        do, lse, delta, scale, causal, S, interpret)
    return (dq[:, :S, :D].astype(q.dtype),
            dk[:, :S, :D].astype(k.dtype),
            dv[:, :S, :D].astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Tiled online-softmax attention with a flash backward.

    Args:
        q, k, v: (B, H, S, D) — MHA layout (expand GQA kv heads per group
            before calling; ``models/attention`` does).
        causal: apply the causal mask (key j visible to query i iff
            j <= i).
        interpret: Pallas interpret mode; None compiles on a TPU and
            interprets elsewhere (:func:`repro.kernels.resolve_interpret`).

    Returns:
        (B, H, S, D) attention output in ``q.dtype``; differentiable via
        the flash backward kernels (probs recomputed from the saved lse).
    """
    B, H, S, D = q.shape
    flat = lambda x: x.reshape(B * H, S, x.shape[-1])
    out = _flash(flat(q), flat(k), flat(v), bool(causal), interpret)
    return out.reshape(B, H, S, D)
