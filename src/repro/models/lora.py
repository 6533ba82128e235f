"""LoRA adapters over any :class:`FLModel` (``client.finetune = "lora"``).

The wrapper freezes the base parameters and exposes a *new* ``FLModel``
whose parameter tree contains only the low-rank adapter factors:

* for every targeted base leaf ``W`` — matricized as ``(L?, d_in, d_out)``
  at the balanced axis split (see :func:`adapter_defs`) — the adapter
  holds ``A`` of shape ``(L?, d_in, r)`` (lecun-normal in ``d_in``, via
  the shared ``_default_init``) and ``B`` of shape ``(L?, r, d_out)``
  initialized to **zero** — so a freshly initialized adapter model
  computes the base forward *exactly* (round 0 starts from the base
  model);
* the forward pass merges on the fly:
  ``W_eff = W + (alpha/rank) * (A @ B).reshape(W.shape)`` — for 2-D
  leaves this is the textbook ``x@W + (alpha/r)*(x@A)@B`` identity;
* the frozen base tree is *closed over* (an ``FLModel`` hashes by
  identity, so jit/lru caches key on the wrapper instance and the base
  leaves become hoisted constants — replicated once per program, never
  per client under ``vmap``).

Because the wrapper *is* an ``FLModel``, every execution engine
(sequential, batched vmap + step loop, async) and every downstream stage
(FedAvg aggregation, STC/int8 in-program compression, error-feedback
residuals, checkpointing, ``comm_up_bytes`` accounting) operates on the
adapter tree with zero changes — a cohort of N clients trains stacked
``(N, d_in, r)`` / ``(N, r, d_out)`` adapter leaves and only adapters
ever hit the wire.

Targeting is driven by the base model's ``ParamDef`` tree: a leaf is
eligible when it has >= 2 dims beyond a leading stacked ``"layers"``
axis (scan-stacked transformer segments get batched adapters with the
same leading axis).  ``targets`` are substring patterns matched against
the "/"-joined tree path; an empty tuple targets every eligible leaf.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, is_paramdef_leaf, zeros_init
from repro.models.small import FLModel

PyTree = Any


def _path_str(path) -> str:
    """Stable "/"-joined key path ("segments/0/attn/wq")."""
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:  # GetAttrKey / fallback
            parts.append(str(getattr(entry, "name", entry)))
    return "/".join(parts)


def _lead(d: ParamDef) -> int:
    """Leading stacked-scan dims ("layers" axis) to batch adapters over."""
    return 1 if (d.axes and d.axes[0] == "layers") else 0


def _eligible(d: ParamDef) -> bool:
    return len(d.shape) - _lead(d) >= 2


def target_paths(defs: PyTree, targets: Sequence[str] = ()) -> Tuple[str, ...]:
    """The "/"-joined paths of the base leaves LoRA will adapt.

    ``targets`` are substring patterns; ``()`` selects every eligible
    (>= 2 matrix dims beyond a stacked "layers" axis) leaf.
    """
    flat = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=is_paramdef_leaf)[0]
    out = []
    for path, d in flat:
        if not _eligible(d):
            continue
        p = _path_str(path)
        if targets and not any(t in p for t in targets):
            continue
        out.append(p)
    return tuple(out)


def adapter_defs(defs: PyTree, rank: int,
                 targets: Sequence[str] = ()) -> Dict[str, Dict[str, ParamDef]]:
    """ParamDef tree of the A/B factors: {path: {"a": ..., "b": ...}}.

    ``A`` keeps the default init (normal with std 1/sqrt(d_in) — its
    fan-in is ``shape[-2]``); ``B`` is zeros, so ``A @ B == 0`` at init.
    """
    if rank < 0:
        raise ValueError(f"lora rank must be >= 0, got {rank}")
    flat = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=is_paramdef_leaf)[0]
    by_path = {_path_str(path): d for path, d in flat}
    out: Dict[str, Dict[str, ParamDef]] = {}
    if rank == 0:
        return out
    for p in target_paths(defs, targets):
        d = by_path[p]
        lead = _lead(d)
        lead_shape = d.shape[:lead]
        dims = d.shape[lead:]
        # Balanced matricization for > 2-dim leaves: split at the axis
        # boundary minimizing d_in + d_out.  This recovers the textbook
        # LoRA factorization on both projection layouts — (d | H·hd) for
        # wq-like (d, H, hd) leaves and (H·hd | d) for wo-like
        # (H, hd, d) leaves — where always splitting after the first dim
        # would degrade wo to a rank-H delta with an enormous B factor.
        split = min(range(1, len(dims)),
                    key=lambda i: math.prod(dims[:i]) + math.prod(dims[i:]))
        d_in = math.prod(dims[:split])
        d_out = math.prod(dims[split:])
        lead_axes = ("layers",) * lead
        out[p] = {
            "a": ParamDef(lead_shape + (d_in, rank),
                          lead_axes + (None, None), dtype=d.dtype),
            "b": ParamDef(lead_shape + (rank, d_out),
                          lead_axes + (None, None), dtype=d.dtype,
                          init=zeros_init),
        }
    return out


def merge_lora(base_params: PyTree, adapters: Dict[str, Dict[str, Any]],
               scale: float) -> PyTree:
    """``W + scale * (A @ B).reshape(W.shape)`` on every adapted leaf.

    With no adapters (rank 0 / no matching target) the base tree is
    returned *unchanged* — bit-identical forward, by construction.
    """
    if not adapters:
        return base_params
    flat, treedef = jax.tree_util.tree_flatten_with_path(base_params)
    merged = []
    for path, w in flat:
        ab = adapters.get(_path_str(path))
        if ab is None:
            merged.append(w)
            continue
        # A@B as r rank-1 updates added in a fixed order, not a matmul: a
        # matmul sums over the rank in an order that changes with a leading
        # client (vmap) axis, so sequential and batched cohorts would drift
        # apart.  The chain fuses into one elementwise pass with no
        # (d_in, r, d_out) temporary, and it stays f32 on the TPU, whose
        # default f32 matmul rounds the factors to bf16.
        a = ab["a"].astype(jnp.float32)
        b = ab["b"].astype(jnp.float32)
        delta = a[..., :, 0, None] * b[..., None, 0, :]
        for k in range(1, a.shape[-1]):
            delta = delta + a[..., :, k, None] * b[..., None, k, :]
        merged.append(
            (w.astype(jnp.float32)
             + jnp.float32(scale) * delta.reshape(w.shape)).astype(w.dtype))
    return jax.tree_util.tree_unflatten(treedef, merged)


def lora_wrap(model: FLModel, base_params: PyTree, rank: int,
              alpha: float = 16.0, targets: Sequence[str] = ()) -> FLModel:
    """Wrap ``model`` so its trainable params are LoRA adapters only.

    The returned :class:`FLModel`'s ``init`` yields the adapter tree
    (``B = 0`` — the wrapped forward starts bit-equal to
    ``model.apply(base_params, x)``), and ``apply`` merges the frozen
    ``base_params`` (closed over; hoisted once per compiled program)
    with the adapters on the fly.
    """
    defs = adapter_defs(model.defs, rank, targets)
    scale = float(alpha) / rank if rank else 0.0
    base_apply = model.apply

    def apply(adapters, x):  # flcheck: hot
        return base_apply(merge_lora(base_params, adapters, scale), x)

    return FLModel(f"{model.name}+lora{rank}", defs, apply,
                   model.num_classes, model.input_shape,
                   is_sequence=model.is_sequence)


def adapter_param_count(model: FLModel, rank: int,
                        targets: Sequence[str] = ()) -> int:
    """Total adapter elements — ``sum(rank * (d_in + d_out))`` over targets."""
    return sum(math.prod(d.shape)
               for ab in adapter_defs(model.defs, rank, targets).values()
               for d in ab.values())


def base_param_count(model: FLModel) -> int:
    leaves = jax.tree_util.tree_flatten(
        model.defs, is_leaf=is_paramdef_leaf)[0]
    return sum(math.prod(d.shape) for d in leaves)
