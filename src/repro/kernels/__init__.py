"""Pallas kernels of the round's hot spots (``ops`` holds the public
wrappers, ``ref`` the pure-jnp oracles)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for one kernel call.

    An explicit bool wins; ``None`` means "compile when the default
    backend is a TPU, interpret otherwise".  It does not look at where the
    arrays are: on a TPU host, kernels called on arrays placed on the CPU
    need ``interpret=True``."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
