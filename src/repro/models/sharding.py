"""Sharding hints that are a no-op outside a mesh context.

Models call ``hint(x, "batch", None, "model")`` with *logical* axis names;
under an ambient mesh (``jax.set_mesh``) this turns into
``with_sharding_constraint``; with no mesh (CPU unit tests) it is a no-op.
Logical axes are resolved through the active rule table so the same model
code serves the single-pod ("data","model") and multi-pod
("pod","data","model") meshes: "batch" -> ("pod","data") when a pod axis
exists, else ("data",).  Mesh axes that cannot take a constraint — manual
axes inside a ``shard_map`` body, or axes that do not divide the dimension
— are left out of the spec in :func:`resolve_spec`, so every constraint
``hint`` emits is one the compiler accepts.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()

# logical -> mesh axis (or tuple); None = replicate
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "vocab": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "expert_cap": None,
    "frames": None,
    # sequence-parallel attention fallback: used for the query-sequence dim
    # when an arch's head counts cannot shard over "model" (MQA, odd heads)
    "qseq": ("model",),
}


def current_rules() -> Dict[str, Optional[Tuple[str, ...]]]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextmanager
def use_rules(rules: Dict[str, Optional[Tuple[str, ...]]]):
    old = current_rules()
    _state.rules = dict(rules)
    try:
        yield
    finally:
        _state.rules = old


def current_mesh():
    """The ambient (abstract) mesh set by ``jax.set_mesh``; None when no
    mesh is active."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes, so sharding constraints and
    propagation behave as GSPMD hints (the default ``Explicit`` axes turn
    every ``with_sharding_constraint`` into a typed-sharding assertion)."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))


def shard_map(f, mesh, in_specs, out_specs, manual_axes=None):
    """Full- or partial-manual ``jax.shard_map``; ``manual_axes`` defaults
    to every mesh axis (full-manual)."""
    manual = set(manual_axes if manual_axes is not None
                 else mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


def resolve_spec(logical: Tuple[Optional[str], ...], shape=None) -> Optional[P]:
    """Resolve logical axis names to a PartitionSpec for the ambient mesh.

    A logical axis stays replicated (``None``) when its rule names no mesh
    axis that is present, not yet used by an earlier dim, and not manual
    (inside ``shard_map`` the body already owns those axes), or when the
    mesh axes' product does not divide ``shape[i]``.  Returns None when no
    mesh is active."""
    mesh = current_mesh()
    if mesh is None:
        return None
    mesh_axes = set(mesh.axis_names) - set(mesh.manual_axes)
    sizes = dict(mesh.shape)  # {axis_name: size}
    rules = current_rules()
    parts = []
    used = set()
    for i, name in enumerate(logical):
        if name is None:
            parts.append(None)
            continue
        target = rules.get(name)
        if target is None:
            parts.append(None)
            continue
        axes = tuple(a for a in target if a in mesh_axes and a not in used)
        if not axes:
            parts.append(None)
            continue
        if shape is not None:
            total = 1
            for a in axes:
                total *= sizes[a]
            if shape[i] % total != 0:
                parts.append(None)
                continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    return P(*parts)


def hint(x, *logical: Optional[str]):
    """with_sharding_constraint on logical axes; no-op without a mesh."""
    spec = resolve_spec(tuple(logical), shape=getattr(x, "shape", None))
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
